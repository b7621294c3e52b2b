"""serve.decode_step_ms: device time per dispatch of the engine's decode
step (``LM.decode_step`` inside the engine's greedy step), from the XLA
module events of the traced window."""
from bench.lib.xtrace import module_mean_ms


def read(run: dict):
    return module_mean_ms(run["trace"], ("greedy_step", "decode_step"))
