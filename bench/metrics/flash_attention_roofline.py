"""Share of its roofline that the prefill flash-attention kernel
reaches in the window's stage calls (:mod:`bench.flops`)."""

from bench.record import kernel_roofline_pct


def read(run):
    return kernel_roofline_pct(run, "flash_attention")
