"""Posterior upload per policy decision, in ms: the mean
``posterior_upload`` span, the upload of the host cache's means and
variances, the sqrt, and the sync that ends the ``posterior`` span."""


def read(run):
    spans = [s["dur_us"] for s in run.spans
             if s["name"] == "posterior_upload"]
    return sum(spans) / len(spans) / 1e3 if spans else None
