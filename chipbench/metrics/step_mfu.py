"""Model FLOPs of the work served in the traced window over the window
times the chip's peak bf16 rate: the accurate rung's prefill once per
call and every output token at its own context and rung.  The other
rung's prefill and the catch-up replay are overhead and do not count."""

from chipbench import costs


def read(rec):
    if rec.trace is None:
        return None
    p = rec.prompt_len
    windows = {r: int(rec.serving[r]["sliding_window"]) for r in rec.serving
               if isinstance(rec.serving[r], dict)}
    per_call = [costs.served_flops(
        rec.arch, batch=rec.batch, prompt_len=p,
        positions=[(p + i, windows[r]) for i, r in enumerate(c.rungs)])
        for c in rec.calls]
    return 100.0 * sum(per_call) / (rec.window_s * rec.peaks["bf16_flops_per_s"])
