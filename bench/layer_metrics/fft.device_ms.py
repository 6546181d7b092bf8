"""fft.device_ms: device milliseconds per step of the ops classed ``fft``
(XLA's FFT, and the four-step Pallas kernel where a plan uses it), per
chip."""


def read(r):
    ns = r.reduction.class_ns["fft"]
    return ns / r.steps * 1e-6 if ns > 0 else None
