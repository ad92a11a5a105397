"""Work counts: the least work the algorithm of one unit needs.

Each module holds ``work(config, traffic) -> {"flops", "bytes"}`` for one
unit of a cell (one fit with its replicates, one sweep, one day), computed
from the shapes alone.  The counts are the algorithm's useful work, not
what the program implements: a symmetric Gram counts its upper triangle
(``n * q * (q + 1)`` operations, multiply and add), a set of fold or
segment Grams over the same rows counts one pass (each row falls in one
cell), and every operand the algorithm must read is read once.  So the
segment one-hot expansion, the 128-lane padding, the re-reads of segment
tiles and the per-fold re-reads are left out, and no correct
implementation can read above 100% of its roofline.
"""

F32 = 4


def sym_gram_flops(n: float, q: float) -> float:
    """Multiply-adds of the upper triangle of an (n x q) Gram, as flops."""
    return float(n) * q * (q + 1)
