"""Spatzformer-JAX: a reconfigurable multi-pod JAX training/inference framework.

Reproduction + extension of "Spatzformer: An Efficient Reconfigurable Dual-Core
RISC-V V Cluster for Mixed Scalar-Vector Workloads" (Perotti et al., 2024),
adapted to TPU v5e multi-pod meshes.

The paper's split/merge reconfigurability is implemented over the mesh `pod`
axis (``repro.core``): SPLIT partitions the fabric into independent sub-mesh
tenants, each with its own controller; MERGE fuses the fabric under a single
controller and frees the remaining controllers for scalar/control work that
overlaps with device compute.
"""

__version__ = "1.0.0"
