"""The dense synray kernel's share of its roofline (%) in the traced
window: useful operations 2*T*R*C per chip and launch, and its operands and
result once, against the chip's bf16 and HBM peaks (bench/peaks.json)."""
from bench.harness.readers import synray_roofline


def read(run):
    return synray_roofline(run)
