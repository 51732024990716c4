"""Roofline share of the MM2IM Pallas kernels: the TCONV layers' least
time at the chip's peaks (per call the larger of counted operations over
peak and counted bytes over HBM bandwidth), over the kernels' own device
time in the traced window.  Only the Mosaic kernel events count
(``kernels.json``); the residue interleave and the crop that follow each
kernel run as XLA ops and are not in the time, so this reads the kernel,
not the whole TCONV layer."""


def read(ctx):
    return ctx.kernel_roofline()
