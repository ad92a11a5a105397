"""One fit: the point fit of DML with ridge nuisances for y and t, plus
B bootstrap refits when the traffic asks for CIs.

Per estimate (the point fit or one replicate) the least work is one
fold-segmented Gram pass over [X | 1 | y | t] (q = p + 3): every fold's
complement Gram, for both nuisances at once, follows from the per-fold
Grams by subtraction.  Reads X, y and t (and a replicate's row weights)
once.  Then the out-of-fold predictions read X once more (2 n (p + 1)
operations for each nuisance), and the final stage is a q = 2 Gram over
the residuals and its HC0 meat (q = 1).
"""

from chipbench.counts import F32, sym_gram_flops


def work(config: dict, traffic: dict) -> dict:
    n, p = config["n"], config["p"]
    B = config["causal_config"]["n_bootstrap"] if traffic["bootstrap"] else 0
    q = p + 3
    per = (sym_gram_flops(n, q) + 2 * 2.0 * n * (p + 1)
           + sym_gram_flops(n, 2) + sym_gram_flops(n, 1))
    per_bytes = F32 * n * (p + 2) + F32 * n * p + F32 * n * 4
    reps = 1 + B
    return {"flops": reps * per,
            "bytes": reps * per_bytes + B * F32 * n}
