from repro.roofline.analysis import (  # noqa: F401
    CHIP_PEAKS,
    ChipPeaks,
    Roofline,
    chip_peaks,
    collective_bytes_from_hlo,
    count_params,
    model_flops,
    roofline_terms,
)
