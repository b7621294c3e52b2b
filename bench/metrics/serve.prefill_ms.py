"""serve.prefill_ms: device time per dispatch of the engine's prefill
programs, from the XLA module events of the traced window: the chunked
prefill (``jit_prefill_chunk``) and the packed prefill, which the engine
jits through ``functools.partial`` (launch/serve.py) and so appears as
``jit__unknown`` until the program names it."""
from bench.lib.xtrace import module_mean_ms


def read(run: dict):
    return module_mean_ms(run["trace"], ("prefill", "jit__unknown"))
