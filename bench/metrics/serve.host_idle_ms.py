"""serve.host_idle_ms: device idle per engine step that the engine's own
host work leaves: the idle time of the traced window whose innermost host
annotation is one of the engine's spans (``serve.step`` and its phases,
launch/serve.py), over the ``serve.step`` spans that start in the window
(bench/lib/layers.py). Also notes how much of the idle under the
driver's ``bench.engine_step`` those spans own."""
from bench.lib import harness as H
from bench.lib.layers import for_run

ENGINE = "serve."


def read(run: dict):
    r = for_run(run)
    steps = r.span_counts.get("serve.step") if r is not None else None
    if not steps:
        return None
    engine = sum(v for k, v in r.idle_by_span.items()
                 if k.startswith(ENGINE))
    under = r.idle_under.get("bench.engine_step", 0.0)
    if under:
        H.note(f"idle under bench.engine_step {under:.4f} s, of which "
               f"{100 * engine / under:.2f}% in the engine's spans")
    return 1e3 * engine / steps
