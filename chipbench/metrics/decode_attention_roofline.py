"""The decode_attention kernel's share of its roofline, in percent."""
from chipbench.readers import roofline


def read(window):
    return roofline(window, "decode_attention")
