"""repro.kernels — Pallas TPU kernels for the compute hot spots.

Each subpackage: kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd public wrapper), ref.py (pure-jnp oracle). Validated in
interpret=True mode on CPU; deployed with interpret=False on TPU (swarm/
and checksum/ resolve it from the platform, ``repro.accel``).

  swarm/      fleet engine tick: masked rarest-argmin, max-min water-fill
  attention/  flash attention fwd (online softmax; causal/window/softcap/GQA)
  rglru/      RG-LRU linear recurrence (Griffin/RecurrentGemma)
  ssd/        Mamba-2 chunked state-space duality
  checksum/   on-device bundle verification (data-integrity fabric)
"""
