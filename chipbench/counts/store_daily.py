"""One day of a windowed moment store: the day's ingest, then the
refresh of a per-cohort DML column (ridge visit, fixed-majorizer MM
logistic treatment) over the window's rows.

The least work: one symmetric (cohort, fold)-segmented Gram pass over
the day's rows alone, [X | 1 | y] (q = p + 2), reading X, y, the cohort
and the fold once; then over the N = days x day_rows rows the store
holds, what a sweep over N rows needs after its fold Gram
(``counts/dml_sweep.py``): per MM step the logits of the row's K fold
models, 2 N K (p + 1) operations, the held-in gradient terms, the same
again, and the own-fold term, 2 N (p + 1), reading X, t, the cohort and
the fold once; the out-of-fold predictions of y and t, 2 N (p + 1)
each, reading X, y, t, the cohort and the fold once; and the final
stage's symmetric Gram of [rt | ry] (q = 2) and its HC0 meat (q = 1).
The window's Gram is the sum of the day slots, which the count leaves
out as the sweep's count leaves out nothing of the kind.
"""

from chipbench.counts import F32, sym_gram_flops


def work(config: dict, traffic: dict) -> dict:
    R, p = config["day_rows"], config["p"]
    N = config["days"] * R
    cc = config["causal_config"]
    k, steps = cc["n_folds"], 2 * cc["newton_iters"]
    flops = (sym_gram_flops(R, p + 2)
             + steps * (2 * 2.0 * N * k * (p + 1) + 2.0 * N * (p + 1))
             + 2 * 2.0 * N * (p + 1)
             + sym_gram_flops(N, 2) + sym_gram_flops(N, 1))
    bytes_ = (F32 * R * (p + 3)            # the day's fold Gram
              + steps * F32 * N * (p + 3)  # MM step: X, t, cohort, fold
              + F32 * N * (p + 4))         # predictions and final stage
    return {"flops": flops, "bytes": bytes_}
