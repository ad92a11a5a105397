"""One segmented sweep: per-cohort DML over E cohorts x K folds with a
ridge visit model and a fixed-majorizer MM logistic treatment model.

The least work: one symmetric (cohort, fold)-segmented Gram pass over
[X | 1 | y] (q = p + 2), from which every fold-complement ridge system
and the majorizer follow by subtraction; per MM step, the logits of
the row's K fold models, 2 n K (p + 1) operations, the held-in
gradient terms, the same again, and the own-fold term, 2 n (p + 1),
reading X, t, the cohort and the fold once; the out-of-fold
predictions of y and t, 2 n (p + 1) each, reading X, y, t, the cohort
and the fold once; and the final stage's two symmetric Grams at q = 2.
"""

from chipbench.counts import F32, sym_gram_flops


def work(config: dict, traffic: dict) -> dict:
    n, p = config["n"], config["p"]
    cc = config["causal_config"]
    k, steps = cc["n_folds"], 2 * cc["newton_iters"]
    flops = (sym_gram_flops(n, p + 2)
             + steps * (2 * 2.0 * n * k * (p + 1) + 2.0 * n * (p + 1))
             + 2 * 2.0 * n * (p + 1)
             + 2 * sym_gram_flops(n, 2))
    bytes_ = (F32 * n * (p + 3)            # fold Gram: X, y, cohort, fold
              + steps * F32 * n * (p + 3)  # MM step: X, t, cohort, fold
              + F32 * n * (p + 4))         # predictions and final stage
    return {"flops": flops, "bytes": bytes_}
