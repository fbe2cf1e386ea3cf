"""repro: a multi-pod JAX training/serving framework built around the
multi-stream transfer/compute-overlap methodology of *Streaming Applications
on Heterogeneous Platforms* (Li et al., 2016)."""

__version__ = "1.0.0"
