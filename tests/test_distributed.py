"""Sharding rules + compressed gradient all-reduce."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed import compressed_psum_mean, dp_axes, spec_for
from repro.distributed.compression import make_compressed_grad_allreduce


class FakeMesh:
    """Mesh-shaped stand-in for rule tests (no devices needed)."""
    def __init__(self, shape, names):
        self.axis_names = names
        import numpy as _np
        self.devices = _np.empty(shape)


M = FakeMesh((16, 16), ("data", "model"))
MP = FakeMesh((2, 16, 16), ("pod", "data", "model"))


def test_tp_divisible_dims_shard():
    assert spec_for((4608, 18432), ("embed", "ff"), M) == P(None, "model")
    assert spec_for((256000, 3584), ("vocab", "embed"), M) == P("model", None)


def test_param_indivisible_replicates_not_relocates():
    # minicpm vocab=122753 is not divisible by 16 -> replicate, never shard
    # a contracting dim (the 80GB-all-reduce lesson, see sharding.py)
    assert spec_for((122753, 2304), ("vocab", "embed"), M,
                    relocate=False) == P(None, None)


def test_cache_relocation_gives_split_kv():
    # 8 kv heads < 16-way model axis -> sequence dim takes the TP shard
    s = spec_for((40, 128, 32768, 8, 128),
                 ("layers", "batch", "seq", "kv_heads", "head_dim"), M,
                 overrides={"batch": ("data",), "kv_heads": "model"})
    assert s == P(None, ("data",), "model", None, None)


def test_long_context_batch1_shards_sequence():
    s = spec_for((6, 1, 524288, 32, 64),
                 ("layers", "batch", "seq", "kv_heads", "head_dim"), M,
                 overrides={"batch": ("data",), "kv_heads": "model"})
    # batch=1 can't shard -> dp relocates to the sequence (SP)
    assert s == P(None, None, ("data",), "model", None)


def test_fsdp_shards_embed_over_data():
    s = spec_for((7168, 4864), ("embed", "ff"), M, fsdp=True,
                 relocate=False)
    assert s == P("data", "model")


def test_dp_axes_multi_pod():
    assert dp_axes(M) == ("data",)
    assert dp_axes(MP) == ("pod", "data")


def test_moe_expert_sharding():
    s = spec_for((128, 7168, 4864), ("experts", "embed", "moe_ff"), M,
                 fsdp=True, relocate=False)
    assert s == P("model", "data", None)


# ---------------------------------------------------------------------------
# compressed gradient all-reduce (error feedback)
# ---------------------------------------------------------------------------


def test_compressed_psum_identity_on_single_shard():
    mesh = jax.make_mesh((1,), ("data",))
    g = jnp.linspace(-1, 1, 64).reshape(8, 8)
    e = jnp.zeros_like(g)

    @jax.shard_map(mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    def run(gl, el):
        return compressed_psum_mean(gl, el, "data")

    mean, err = run(g, e)
    # single shard: mean == dequantized(quantized(g)); err = residual
    np.testing.assert_allclose(np.asarray(mean + err), np.asarray(g),
                               rtol=0, atol=1e-6)
    assert float(jnp.abs(err).max()) <= float(jnp.abs(g).max()) / 127 + 1e-6


def test_error_feedback_reduces_bias_over_steps():
    """With error feedback, the accumulated compressed sum tracks the true
    sum (residual stays bounded instead of drifting)."""
    mesh = jax.make_mesh((1,), ("data",))
    g = jnp.full((16,), 0.003)   # much smaller than max-scale step
    e = jnp.zeros_like(g)
    acc_true, acc_comp = 0.0, 0.0

    @jax.shard_map(mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()))
    def run(gl, el):
        return compressed_psum_mean(gl, el, "data")

    for _ in range(50):
        mean, e = run(g, e)
        acc_true += float(g[0])
        acc_comp += float(mean[0])
    assert abs(acc_comp - acc_true) / acc_true < 0.05


def test_tree_allreduce_wrapper():
    mesh = jax.make_mesh((1,), ("data",))
    fn = make_compressed_grad_allreduce(mesh)
    grads = {"a": jnp.ones((4, 4)), "b": jnp.full((3,), -2.0)}
    errs = jax.tree.map(jnp.zeros_like, grads)
    mean, new_err = fn(grads, errs)
    np.testing.assert_allclose(np.asarray(mean["a"]), 1.0, atol=0.02)
    np.testing.assert_allclose(np.asarray(mean["b"]), -2.0, atol=0.04)
