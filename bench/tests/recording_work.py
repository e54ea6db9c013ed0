"""A configuration's own work counts for the test that adds a configuration
through new files alone: the dense counts, recording each call."""
from bench import work as dense

CALLS = []


def prefill_flops(m, n):
    CALLS.append("prefill_flops")
    return dense.prefill_flops(m, n)


def decode_flops(m, keys):
    CALLS.append("decode_flops")
    return dense.decode_flops(m, keys)


def decode_bytes(m, keys):
    CALLS.append("decode_bytes")
    return dense.decode_bytes(m, keys)
