"""repro: Masked VByte for TPU — multi-pod JAX training/inference framework.

Reproduction + TPU adaptation of Plaisance, Kurz & Lemire, "Vectorized VByte
Decoding" (2015), with the decoder integrated as a first-class compressed
integer substrate for LM / GNN / RecSys workloads. See README.md and
docs/kernels.md.
"""

__version__ = "0.1.0"
