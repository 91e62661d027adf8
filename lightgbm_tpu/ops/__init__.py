"""Device ops: histograms, split search, partition, prediction."""

import jax

# Precision of every f32 matmul on the training path (histogram one-hot
# accumulations and the prefix-sum triangular matmuls, in XLA and inside the
# Pallas kernels).  At the default precision the MXU rounds f32 operands to
# bf16: measured on the v5e (PR 21) the prefix-sum matmul returned integer
# counts < 50 000 off by up to 2620, which breaks the counts and the split
# gains without any error.  HIGHEST keeps counts exact below 2^24 and g/h
# sums within f32 reassociation of a serial scan.  (bf16 operands — the
# quantized integer carriers — are exact at any setting.)
F32_DOT_PRECISION = jax.lax.Precision.HIGHEST

# Scoped VMEM one Pallas kernel may take on the v5e without asking the
# compiler for more (its default limit; the kernels ask for none).  Every
# kernel module has a ``vmem_bytes`` that reckons what its kernel takes at
# a shape, and models/plan.py names no kernel whose reckoning is over this.
VMEM_LIMIT_BYTES = 16 << 20


def varying_like(shape, dtype, *operands):
    """``ShapeDtypeStruct`` of a ``pallas_call`` output that varies over
    the mesh axes any of ``operands`` varies over: inside ``shard_map``
    (tree_learner=data runs the kernels on each shard's own rows and its
    own start/count) the output's type has to say so; outside, the set is
    empty."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in operands))
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
