"""Pallas TPU kernels for the perf-critical compute layers.

embedding_bag : fused gather+pool — the TPU-native analogue of the
                paper's NMP-DIMM near-memory reduction (the shard kernels
                DMA each bag's rows HBM->VMEM and reduce them in VMEM;
                the gathered matrix never exists in HBM).
flash_attention : causal blocked attention for train/prefill.
flash_decode  : KV-block decode attention emitting (o, l, m) partials —
                the kernel under the sequence-sharded cache's Fsum
                combine.

All kernels are validated on CPU in interpret mode against ref.py; on a
TPU backend ``ops`` runs them compiled, and any other backend raises.
"""
