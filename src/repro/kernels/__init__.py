"""Pallas TPU kernels for the paper's compute hot-spots (NVFP4 quantize +
grouped FP4 expert FFN), their shared numerics in nvfp4.py and the serving
entry points in ops.py."""
