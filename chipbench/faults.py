"""Faults planted in the program under test, each of which the fit
cells' check must come out not correct on.

Each fault is ``fault(patch)``, where ``patch(obj, name, value)`` sets an
attribute (pytest's ``monkeypatch.setattr``, or ``setattr`` in a process
that runs nothing else).  The CPU tests plant each one and see
``correct`` false; ``control.py --fault <name>`` reads the compared
numbers with one planted, on the chip at the cell's own size, for the
upper readings of the limits (PERF.md).
"""

from __future__ import annotations

import dataclasses


def _half(*arrays):
    return [a[: a.shape[0] // 2] for a in arrays]


def fit_half_rows(patch):
    """The point fit (and so its replicates) sees half of the rows."""
    from repro.core import dml
    fit = dml.DML.fit

    def half(self, y, t, X, W=None, key=None):
        y, t, X = _half(y, t, X)
        return fit(self, y, t, X, W=W, key=key)

    patch(dml.DML, "fit", half)


def fit_altered_theta(patch):
    """The point fit's theta is off by 1% where it is produced."""
    from repro.core import dml
    fit = dml.DML.fit

    def altered(self, *a, **kw):
        res = fit(self, *a, **kw)
        return dataclasses.replace(res, theta=res.theta * 1.01)

    patch(dml.DML, "fit", altered)


def replicate_half_rows(patch):
    """Each bootstrap replicate refits on half of the rows."""
    from repro.inference import bootstrap
    make = bootstrap.make_dml_replicate_fn

    def make_half(*a, **kw):
        inner = make(*a, **kw)

        def replicate(kb, XW, y, t, phi):
            return inner(kb, *_half(XW, y, t, phi))

        return replicate

    patch(bootstrap, "make_dml_replicate_fn", make_half)


def replicate_lineage(patch):
    """Replicate b gets replicate b + 1's key."""
    from repro.inference import bootstrap
    keys = bootstrap.replicate_keys
    patch(bootstrap, "replicate_keys", lambda key, n: keys(key, n + 1)[1:])


FAULTS = {f.__name__: f for f in (fit_half_rows, fit_altered_theta,
                                  replicate_half_rows, replicate_lineage)}
