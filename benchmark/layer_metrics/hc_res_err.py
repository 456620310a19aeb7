"""Graph layer: what the 20 Sinkhorn iterations leave — the largest
``|rowsum - 1|`` or ``|colsum - 1|`` of any sub-layer's mix over a display's
tokens (the HC_MAP layers' ``<p>hc_{a,f}_res_err`` tops), the largest display
of the window. It reads about a thousandth on a young model and grows as the
mappings train (20 iterations on a diagonal of e^4 converge slowly, and the
reference leaves the same); ``correct`` holds every display's under 0.05 and
the program's remainder to the reference's on the same weights
(``res_err_rel``)."""

import lm_trace


def reduce(run: dict):
    errs = lm_trace.section(run).get("hc_res_err")
    return max(errs) if errs else None
