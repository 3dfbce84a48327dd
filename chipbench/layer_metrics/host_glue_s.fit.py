"""Estimator API and host glue: the ``<Estimator>.fit`` span minus its
``preprocess`` and ``fit.dispatch`` children, seconds per fit."""
from chipbench import spans


def read(ctx):
    roots = spans.fits(ctx)
    if not roots:
        return None
    pre, dis = spans.mean_child_seconds(ctx, "preprocess"), spans.mean_child_seconds(ctx, "dispatch")
    return sum(r["dur"] for r in roots) * 1e-6 / len(roots) - pre - dis
