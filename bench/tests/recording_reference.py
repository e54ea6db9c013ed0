"""A configuration's own reference for the test that adds a configuration
through new files alone: the dense reference, recording each call."""
from bench import reference as dense

CALLS = []


def model_block(cfg):
    CALLS.append("model_block")
    return dense.model_block(cfg)


def make_weights(m, seed):
    CALLS.append("make_weights")
    return dense.make_weights(m, seed)


def logits_at(m, w, tokens, rows_b, rows_p, quant=None):
    CALLS.append(("logits_at", quant))
    return dense.logits_at(m, w, tokens, rows_b, rows_p, quant=quant)
