"""Share of the packed-prefill token slots that were padding: the
engine's ``prefill_padded_tokens`` over ``prefill_token_slots``."""


def read(trace, counters, spans, cell):
    slots = counters.get("prefill_token_slots")
    return 100.0 * counters["prefill_padded_tokens"] / slots \
        if slots else None
