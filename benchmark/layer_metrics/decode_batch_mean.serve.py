"""Tokens a decode step produced, on average over the window: the
engine's ``tokens_generated`` over its ``decode_steps``."""


def read(trace, counters, spans, cell):
    steps = counters.get("decode_steps")
    return counters["tokens_generated"] / steps if steps else None
