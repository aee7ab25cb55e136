"""One number the program wrote on the main program's compile events
(`paddle_tpu.observe.observatory()`, `detail`), by its key: what the
executor knew of the program when it built the step. `grad_fanin_max` is the
most gradient contributions `core/backward.py` summed into one parameter
(4 where a stack of layers is applied four times under shared weights, 1
where every weight is used once, so it holds that weights are shared and not
copied); `parameters` and `parameter_uses` are beside it. The newest event
that carries the key is read. Nothing where the program keeps no such
record, as a program older than the key does not."""


def program_detail(events, program_uid, key):
    """The value under `key` on the program's newest compile event that has
    one; None if none does."""
    for e in reversed(events):
        detail = getattr(e, "detail", None)
        if e.program_uid == program_uid and isinstance(detail, dict) \
                and key in detail:
            return detail[key]
    return None


def read(ctx, key):
    from paddle_tpu import observe
    value = program_detail(observe.observatory().events(),
                           ctx["system"].main._uid, key)
    return None if value is None else float(value)
