"""fftx1y1: magnitude of each image's 2-D FFT."""


def kernel(P, c, s):
    return P.fft2_abs(c["img"])
