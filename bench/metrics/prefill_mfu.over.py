"""Execution, in the above-knee cells: FLOPs of the real (unpadded) rows of
every ``ReplicaEngine.execute_batch`` call over those calls' host time
times the chip's bf16 peak."""

from bench import flops


def read(ctx):
    return flops.prefill_mfu(ctx)
