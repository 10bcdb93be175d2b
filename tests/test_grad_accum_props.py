"""Property tests for core/grad_accum.py: split_microbatches round-trip,
accumulation linearity, non-divisible-batch behavior, and the narrowed
_constrain_tree no-mesh handling (ZeRO-2's reduce-scatter constraint must
never be silently dropped under a live mesh)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")  # dev-only dep (requirements-dev.txt)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import grad_accum
from repro.core.grad_accum import accumulate_gradients, split_microbatches
from repro.launch.mesh import make_local_mesh

SETTINGS = dict(max_examples=25, deadline=None)


# ---------------------------------------------------------------------------
# split_microbatches
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(accum=st.sampled_from([1, 2, 4, 8]),
       per_mb=st.integers(1, 4),
       trailing=st.sampled_from([(), (3,), (2, 5)]),
       seed=st.integers(0, 2 ** 16))
def test_split_microbatches_round_trip(accum, per_mb, trailing, seed):
    """Reshape inverse: concatenating the microbatches restores the batch,
    leaf by leaf, in order."""
    b = accum * per_mb
    key = jax.random.PRNGKey(seed)
    batch = {"x": jax.random.normal(key, (b,) + trailing),
             "y": jnp.arange(b, dtype=jnp.int32)}
    mbs = jax.tree.map(np.asarray, split_microbatches(batch, accum))
    for k, leaf in batch.items():
        assert mbs[k].shape == (accum, per_mb) + leaf.shape[1:]
        np.testing.assert_array_equal(
            mbs[k].reshape(leaf.shape), np.asarray(leaf))


@settings(**SETTINGS)
@given(accum=st.sampled_from([1, 3, 5]), seed=st.integers(0, 2 ** 16))
def test_split_microbatches_scalar_leaf_broadcast(accum, seed):
    """Scalar leaves (step counters, shared flags) broadcast to (accum,), so
    every microbatch sees the same value."""
    val = jnp.float32(seed)
    mbs = split_microbatches({"x": jnp.zeros((accum, 2)), "s": val}, accum)
    assert mbs["s"].shape == (accum,)
    np.testing.assert_array_equal(np.asarray(mbs["s"]),
                                  np.full((accum,), float(seed), np.float32))


@pytest.mark.parametrize("batch,accum", [(6, 4), (3, 2), (8, 3)])
def test_split_microbatches_non_divisible_asserts(batch, accum):
    with pytest.raises(AssertionError):
        split_microbatches({"x": jnp.zeros((batch, 2))}, accum)


# ---------------------------------------------------------------------------
# accumulation linearity
# ---------------------------------------------------------------------------

@settings(**SETTINGS)
@given(accum=st.sampled_from([2, 4, 8]), seed=st.integers(0, 2 ** 16))
def test_accum_linearity(accum, seed):
    """Mean-of-microbatch-grads == single-shot grads (fp32 tolerance) for a
    mean-reduced loss: DeepSpeed's accumulation contract is exact."""
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    w = jax.random.normal(ks[0], (8, 4))
    batch = {"x": jax.random.normal(ks[1], (16, 8)),
             "y": jax.random.normal(ks[2], (16, 4))}

    def loss_fn(params, b):
        pred = jnp.tanh(b["x"] @ params)
        loss = jnp.mean((pred - b["y"]) ** 2)
        return loss, {"loss": loss}

    g1, _ = accumulate_gradients(loss_fn, w, batch, 1)
    gk, _ = accumulate_gradients(loss_fn, w, batch, accum)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(g1),
                               atol=1e-6, rtol=1e-5)


# ---------------------------------------------------------------------------
# _constrain_tree error narrowing
# ---------------------------------------------------------------------------

def test_constrain_tree_no_mesh_warns_once_and_passes_through(monkeypatch):
    from jax.sharding import PartitionSpec as P

    monkeypatch.setattr(grad_accum, "_warned_no_mesh", False)
    x = {"w": jnp.ones((4, 2))}
    specs = {"w": P("data")}

    @jax.jit
    def f(x):
        return grad_accum._constrain_tree(x, specs)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = f(x)                     # no mesh installed -> warn, not raise
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(x["w"]))
    msgs = [w for w in caught if issubclass(w.category, RuntimeWarning)
            and "no mesh installed" in str(w.message)]
    assert len(msgs) == 1, [str(w.message) for w in caught]


def test_constrain_tree_reraises_non_mesh_errors():
    """A genuinely bad spec (not the no-mesh case) must surface, not be
    swallowed — that is how ZeRO-2's reduce-scatter was silently lost."""
    mesh = make_local_mesh()
    from jax.sharding import PartitionSpec as P

    x = {"w": jnp.ones((4, 2))}
    specs = {"w": P("nonexistent_axis")}
    with mesh:
        with pytest.raises((ValueError, KeyError)):
            jax.jit(lambda x: grad_accum._constrain_tree(x, specs))(x)


def test_constrain_tree_applies_under_mesh():
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_local_mesh()
    x = {"w": jnp.ones((4, 2))}
    fn = jax.jit(lambda x: grad_accum._constrain_tree(x, {"w": P("data")}))
    with mesh:
        lowered = fn.lower(x).as_text()
        out = fn(x)
    # on one device the output sharding normalizes to replicated, so the
    # constraint is checked where it is emitted
    assert 'sharding_constraint %arg0 <@mesh, [{"data"}, {}]>' in lowered
    assert out["w"].sharding.is_equivalent_to(
        NamedSharding(mesh, P("data")), x["w"].ndim)


# ---------------------------------------------------------------------------
# fp32 accumulation under bf16 compute (the cast_params_bf16 contract)
# ---------------------------------------------------------------------------

def test_accumulation_stays_fp32_under_bf16_params():
    """accumulate_gradients must return fp32 accumulators even when the
    compute params (and hence per-microbatch grads) are bf16."""
    params = {"w": jnp.ones((8, 4), jnp.bfloat16)}
    batch = {"x": jnp.ones((8, 8), jnp.bfloat16)}

    def loss_fn(p, b):
        loss = jnp.mean((b["x"] @ p["w"]) ** 2)
        return loss.astype(jnp.bfloat16), {}

    g, _ = accumulate_gradients(loss_fn, params, batch, 4)
    assert g["w"].dtype == jnp.float32


def test_pipeline_grads_stay_fp32_under_bf16_params():
    """The staged 1F1B path accumulates per-chunk VJP cotangents in fp32
    regardless of compute dtype — what makes cast_params_bf16 legal under
    pipeline parallelism (fp32 master grads from bf16 stage compute)."""
    from repro.configs import get_smoke_config
    from repro.core import pipeline
    from repro.launch.specs import concrete_batch
    from repro.models import transformer as model

    cfg = get_smoke_config("vit-b16").replace(dtype="float32", num_layers=2)
    params = model.init_params(cfg, jax.random.PRNGKey(0))
    bf16 = jax.tree.map(
        lambda p: p.astype(jnp.bfloat16)
        if p.dtype == jnp.float32 and p.ndim >= 2 else p, params)
    batch = concrete_batch(cfg, 4, 32, seed=0)
    (_, _), grads = pipeline.pipelined_value_and_grad(
        cfg, bf16, batch, stages=2, num_micro=2, pipe_axis=None)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert g.dtype == jnp.float32, jax.tree_util.keystr(path)


# ---------------------------------------------------------------------------
# per-microbatch rng threading (the TrainState rng plumbing)
# ---------------------------------------------------------------------------

def test_rngs_are_inert_for_deterministic_losses():
    """Passing rngs to a loss that ignores them must not change gradients
    (the engine always threads them; deterministic archs DCE the stream)."""
    params = {"w": jnp.arange(4.0)}
    batch = {"x": jnp.arange(8.0).reshape(8, 1)}

    def loss_no_rng(p, mb):
        return jnp.mean(mb["x"] * p["w"]), {}

    def loss_rng(p, mb, rng):
        del rng
        return loss_no_rng(p, mb)

    g0, _ = accumulate_gradients(loss_no_rng, params, batch, 4)
    rngs = jax.random.split(jax.random.PRNGKey(0), 4)
    g1, _ = accumulate_gradients(loss_rng, params, batch, 4, rngs=rngs)
    np.testing.assert_array_equal(np.asarray(g0["w"]), np.asarray(g1["w"]))


@pytest.mark.parametrize("accum", [1, 4])
def test_rngs_deliver_per_microbatch_keys(accum):
    """Each microbatch must see ITS key: a loss whose gradient is the
    rng draw itself reconstructs exactly the mean over the key stack."""
    params = {"w": jnp.zeros(())}
    batch = {"x": jnp.zeros((accum,))}
    rngs = jax.random.split(jax.random.PRNGKey(7), accum)

    def loss(p, mb, rng):
        return p["w"] * jax.random.uniform(rng, ()), {}

    g, _ = accumulate_gradients(loss, params, batch, accum, rngs=rngs)
    want = np.mean([float(jax.random.uniform(r, ())) for r in rngs])
    np.testing.assert_allclose(float(g["w"]), want, rtol=1e-6)
