"""TPU-native distributed deep-learning framework.

A brand-new JAX/XLA/pjit/shard_map framework with the capabilities of the
reference repo ``Sanasar1/Learning-Deep-Neural-Network-In-Distributed-
Computing-Environment`` (six copied PyTorch variant directories), rebuilt as
ONE configurable framework:

- local-SGD / FedAvg-style data parallelism (sync once per global epoch),
- a 12-mode sync matrix: aggregate {gradients, weights} x {equal, weighted}
  x topology {allreduce, ring, double_ring}  (reference:
  ``Balanced All-Reduce/trainer.py:141-150``, ``.../communication.py``),
- heterogeneity-aware adaptive data partitioning driven by a timing probe
  (reference: ``Balanced All-Reduce/dataloader.py:119-153``),
- straggler time-limit protocol (reference: ``Balanced All-Reduce/
  trainer.py:42-44,112-139``) re-designed as a masked fixed step budget,
- non-IID fixed-class shard injection (reference: ``Disbalanced All-Reduce/
  dataloader.py:56-155``),
- distributed metric collection + the reference's six plots.

The compute path is jit/shard_map over a ``jax.sharding.Mesh`` with XLA
collectives (psum/pmean/ppermute/all_gather) over ICI/DCN — no NCCL/MPI.

The canonical import alias is::

    import learning_deep_neural_network_in_distributed_computing_environment_tpu as ldnde_tpu
"""

__version__ = "0.1.0"

# Subpackages (models, ops, parallel, data, utils) and modules (config, mesh,
# comms, train, eval, viz, probe, checkpoint, main) are imported explicitly by
# users; keep the package root import cheap (no jax import at package import
# time so that tests can set XLA_FLAGS first).

__all__ = ["__version__"]
