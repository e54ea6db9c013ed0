"""Model decode: the least time the chip could take for the traced decode
steps, each the larger of its operations over peak FLOP/s and its bytes
(weights once, live K/V) over HBM bandwidth, over the device time of
``decode_step``. ``bound`` says which of the two bounds it."""
from bench.stats import share_pct


def bound(run, step):
    t_flops = run.work.decode_flops(run.model, step.keys) \
        / run.peaks["bf16_flops_per_s"]
    t_bytes = run.work.decode_bytes(run.model, step.keys) \
        / run.peaks["hbm_bytes_per_s"]
    return max(t_flops, t_bytes), ("compute" if t_flops > t_bytes
                                   else "memory")


def read(run):
    if run.trace is None:
        return None
    steps = run.traced_decodes()
    dev_s = run.device_seconds("decode_step")
    if not steps or dev_s is None:
        return None
    return share_pct(sum(bound(run, s)[0] for s in steps), dev_s)
