"""Model prefill: useful operations of the prompts prefilled in the traced
window, at their true lengths, over the device time of ``prefill_step``
times the chip's peak. Nothing where the trace holds no prefill, or where
its prefills do not match the admissions the loop saw."""
from bench.stats import share_pct


def read(run):
    if run.trace is None:
        return None
    lens = run.traced_prefills()
    dev_s = run.device_seconds("prefill_step")
    if not lens or dev_s is None:
        return None
    flops = sum(run.work.prefill_flops(run.model, k) for k in lens)
    return share_pct(flops, dev_s * run.peaks["bf16_flops_per_s"])
