"""Pallas TPU kernels (validated in interpret mode on CPU):
streamed_matmul, flash_attention, paged_attention (decode from the paged KV
pool), fwt, nw_tile — each with a jit wrapper in ops.py and a pure-jnp
oracle in ref.py."""

from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
