"""gtopkssgd_tpu — a TPU-native framework for gTop-k sparsified synchronous SGD.

A from-scratch JAX/XLA/Pallas rebuild of the capabilities of the reference
repo hclhkbu/gtopkssgd (gTop-k S-SGD, Shi et al., ICDCS 2019, arXiv:1901.04359):
synchronous data-parallel SGD where each step every replica

  1. accumulates its dense gradient into a local error-feedback residual,
  2. selects the local top-k elements by magnitude (k = density * num_params),
  3. runs a tree-structured sparse allreduce ("gTop-k") producing one global
     set of k (index, value) pairs in O(k log P) communication,
  4. applies only those k values, returning globally-rejected values to the
     residual.

Instead of the reference's PyTorch + mpi4py + CUDA stack this package is
TPU-first: pure-functional train steps under `jax.jit`, SPMD over a
`jax.sharding.Mesh` data-parallel axis, `lax.ppermute` hypercube exchanges
riding ICI instead of MPI Send/Recv, `lax.top_k`/Pallas for k-selection
instead of `torch.topk`, and the error-feedback residual carried as
optimizer state inside one pytree (so checkpointing captures it — unlike
the reference, which silently dropped residuals on resume).

Layer map (mirrors SURVEY.md; reference layer in parens):

  gtopkssgd_tpu.trainer        -- single-replica trainer   (L3  dl_trainer.py)
  gtopkssgd_tpu.dist_trainer   -- distributed driver       (L4  dist_trainer.py)
  gtopkssgd_tpu.optimizer      -- distributed optimizer    (L2  optimizer wrapper)
  gtopkssgd_tpu.compression    -- top-k + error feedback   (L2  compression.py)
  gtopkssgd_tpu.parallel       -- sparse collectives       (L1  allreducer.py)
  gtopkssgd_tpu.models         -- model zoo                (C7  vgg/resnet/lstm*)
  gtopkssgd_tpu.data           -- data pipelines           (C8)
  gtopkssgd_tpu.ops            -- top-k / sparse kernels   (torch.topk CUDA)
  gtopkssgd_tpu.native         -- C++ host-side runtime    (torchvision/OpenMPI native code)
  gtopkssgd_tpu.utils          -- timers/logging/ckpt      (L0 settings.py, utils.py)
"""

__version__ = "0.1.0"
