"""Model decode, whole step: useful operations of the decode steps in the
traced window (active slots, live keys, real vocabulary) over the device
time of ``decode_step`` times the chip's peak."""
from bench.stats import share_pct


def read(run):
    if run.trace is None:
        return None
    steps = run.traced_decodes()
    dev_s = run.device_seconds("decode_step")
    if not steps or dev_s is None:
        return None
    flops = sum(run.work.decode_flops(run.model, s.keys) for s in steps)
    return share_pct(flops, dev_s * run.peaks["bf16_flops_per_s"])
