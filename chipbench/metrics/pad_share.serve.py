"""Padding rows over all rows the engine packed in the window
(``PagedServeEngine.serving_report()``)."""


def read(run):
    real, pad = run.data["rows_real"], run.data["rows_padded"]
    return 100.0 * pad / (real + pad) if real + pad else None
