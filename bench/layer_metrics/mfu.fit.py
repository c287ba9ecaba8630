"""The whole fit's share of the chip's peak: the least kernel work that
confirms the returned solution's KKT, one evaluation of Q alpha,
(2d + 3) n n_sv operations (for an early-stopped fit, the sum over the
stopping level's clusters of (2d + 3) n_c n_sv,c), over the traced fit's
seconds times the bf16 peak."""


def read(inputs):
    c = inputs.counters
    if inputs.peaks is None or "confirm_flops" not in c:
        return None
    return 100.0 * c["confirm_flops"] / (c["fit_wall_s"]
                                         * inputs.peaks["flops_per_s"])
