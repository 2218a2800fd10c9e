"""The `rloo_combine` Pallas kernel's share of its roofline, %: the least
time the chip could take for what the caller needs (counts/rloo_combine:
the LM round uploads the mean and drops the reshaped tile) over the
kernel's device time per call.  Memory-bound at K = 2."""
from chipbench.counts import dense_lm, rloo_combine


def read(r):
    t = r.red.kernel_s("rloo")
    if t <= 0:
        return None
    k, n = r.run.traffic["k_micro"], dense_lm.n_params(r.run.config)
    t_min = max(rloo_combine.bytes_needed(k, n, tile_consumed=False)
                / r.peak["hbm_bytes_per_s"],
                rloo_combine.flops(k, n) / r.peak["flops_bf16"])
    return 100.0 * t_min / (t / r.rounds)
