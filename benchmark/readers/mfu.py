"""Model FLOP/s utilization, %: model FLOPs per example (forward and
backward from the configuration's shapes, nothing recomputed; see `flops/`)
times the examples per second of this run's un-profiled window, over chips
times the published bf16 peak of the device kind (`peaks.json`)."""

from readers import step_rate


def read(ctx):
    rate = step_rate.read(ctx)
    if rate is None or ctx["peaks"] is None:    # a rehearsal has no peak
        return None
    achieved = ctx["flops"]["forward_backward"] * rate
    return 100.0 * achieved / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
