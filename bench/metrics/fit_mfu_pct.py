"""fit_mfu_pct: the FLOPs a FALKON fit requires (harness.work.fit_flops: t + 1
sweeps, the Gram, two Cholesky factors, T T^T, the CG triangular solves;
no diagnostic sweeps, no padding) over the traced fit's host seconds times
the chip's published peak, in percent."""


def read(obs):
    if "fit_s" not in obs:
        return None
    return 100.0 * obs["fit_flops"] / (obs["fit_s"] * obs["peaks"]["flops_per_s"])
