"""Share of the roofline reached by the accurate rung's prefill: its least
time (``Arch.prefill_cost`` at the cell's batch and prompt, full attention,
bf16 cache, the serving dtype) over the median device time of its traced
runs; ``None`` without a traced prefill run of that rung."""

import statistics

from chipbench import costs


def read(rec):
    times = rec.trace and rec.trace.step_times("prefill", "accurate")
    if not times:
        return None
    least = rec.arch.prefill_cost(
        batch=rec.batch, prompt_len=rec.prompt_len, window=0,
        kv_dtype="bfloat16", dtype=rec.serving["dtype"]).least_s(rec.peaks)
    return 100.0 * least / statistics.median(times)
