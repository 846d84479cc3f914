from repro.roofline.analysis import HW, roofline_terms  # noqa: F401
