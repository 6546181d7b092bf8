"""fft_roofline: the least time the chip needs for the FFT stages' nominal
work (bench/workcount.py: the larger of bytes over HBM peak and flops over
the peak of bench/peaks.py), over the device time of the ops classed
``fft``, in percent.  Per chip: the work is split evenly over the chips."""


def read(r):
    ns = r.reduction.class_ns["fft"]
    if ns <= 0 or r.peaks is None:
        return None
    least_s, _bound = (r.work * (1.0 / r.chips)).least_seconds(
        r.peaks.flops_per_s, r.peaks.hbm_bytes_per_s)
    return 100.0 * least_s / (ns * 1e-9 / r.steps)
