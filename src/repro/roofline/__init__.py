from repro.roofline.analysis import (PEAKS, parse_collectives,  # noqa: F401
                                     peaks, roofline_terms, model_flops)
