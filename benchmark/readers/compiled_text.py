"""Collectives per step, counted in the optimized HLO of the compiled mesh
step (exact; async `-start`/`-done` pairs count once). Nothing on one chip."""

KINDS = ("all-reduce", "all-gather", "collective-permute", "reduce-scatter",
         "all-to-all")


def inventory(hlo_text):
    inv = {}
    for kind in KINDS:
        n = hlo_text.count(f" {kind}(") + hlo_text.count(f" {kind}-start(")
        if n:
            inv[kind] = n
    return inv


def read(ctx):
    text = ctx["compiled_text"]()
    if text is None:
        return None
    return float(sum(inventory(text).values()))
