"""repro: LightRidge (DONN compilation framework) reproduction in JAX.

Subpackages:
- core:    the paper's contribution (optical physics kernels, DSL, DSE, codesign)
- kernels: Pallas TPU kernels of the LM stack (RoPE, selective scan)
- models:  assigned LM-family architectures (dense/MoE/VLM/audio/SSM/hybrid)
- runtime: distributed runtime (sharding rules, train/serve steps)
- optim:   optimizers, schedules, gradient compression
- checkpoint: sharded fault-tolerant checkpointing
- data:    deterministic synthetic data pipelines
- configs: one config per assigned architecture (+ the paper's own DONNs)
- launch:  mesh / dryrun / train / serve entry points
"""
__version__ = "1.0.0"
