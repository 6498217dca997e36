"""Per-architecture smoke tests (reduced configs) + decode/forward consistency."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (SHAPES, cell_supported, get_arch, list_archs,
                          padded_vocab, param_shapes, reduced)
from repro.models.model import (Runtime, decode_step, forward,
                                init_decode_caches, init_params, loss_fn)

ARCHS = [a for a in list_archs() if a != "gpt2"]
RT = Runtime(mesh=None, compute_dtype=jnp.float32)


def make_batch(cfg, B=2, S=32, seed=0):
    key = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(key)
    if cfg.input_mode == "tokens":
        return {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
                "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    return {"embeddings": 0.1 * jax.random.normal(k1, (B, S, cfg.d_model)),
            "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_shapes_no_nan(arch):
    cfg = reduced(get_arch(arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    logits, aux = jax.jit(lambda p, b: forward(p, cfg, RT, b))(params, batch)
    assert logits.shape == (2, 32, padded_vocab(cfg))
    assert not bool(jnp.isnan(logits).any())
    assert not bool(jnp.isnan(aux))


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    from repro.config import TrainConfig
    from repro.train.step import init_train_state, make_optimizer_for, \
        make_train_step

    cfg = reduced(get_arch(arch))
    tcfg = TrainConfig(learning_rate=1e-3, total_steps=10, warmup_steps=2)
    from repro.train.step import make_optimizer_for
    opt = make_optimizer_for(tcfg)
    state = init_train_state(jax.random.PRNGKey(0), cfg, opt)
    step = jax.jit(make_train_step(cfg, RT, opt))
    batch = make_batch(cfg)
    state2, m1 = step(state, batch)
    state3, m2 = step(state2, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])  # same batch: must descend
    assert int(state3.step) == 2


@pytest.mark.parametrize("arch", ARCHS)
def test_param_count_matches_analytic(arch):
    cfg = reduced(get_arch(arch))
    params = init_params(jax.random.PRNGKey(0), cfg)
    actual = sum(x.size for x in jax.tree.leaves(params))
    import math
    analytic = sum(math.prod(s) for s in param_shapes(cfg).values())
    assert actual == analytic


DECODE_ARCHS = [a for a in ARCHS if get_arch(a).has_decode]


@pytest.mark.parametrize("arch", DECODE_ARCHS)
def test_decode_matches_forward(arch):
    """Token-by-token decode with caches must reproduce the full forward —
    the strongest cache-correctness invariant (covers GQA/rolling-SWA/MLA
    absorbed decode/SSM state/hybrid shared-attn caches)."""
    cfg = reduced(get_arch(arch))
    if cfg.ssm_state:
        # decode path needs seq % chunk alignment only for forward
        pass
    params = init_params(jax.random.PRNGKey(1), cfg)
    B, S = 2, 16
    batch = make_batch(cfg, B=B, S=S, seed=3)
    full_logits, _ = jax.jit(lambda p, b: forward(p, cfg, RT, b))(params, batch)

    caches = init_decode_caches(cfg, B, S, dtype=jnp.float32)
    step = jax.jit(lambda p, b, c, i: decode_step(p, cfg, RT, b, c, i))
    outs = []
    for t in range(S):
        if cfg.input_mode == "tokens":
            tb = {"tokens": batch["tokens"][:, t: t + 1]}
        else:
            tb = {"embeddings": batch["embeddings"][:, t: t + 1]}
        logits, caches = step(params, tb, caches, jnp.int32(t))
        outs.append(logits[:, 0])
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(full_logits),
                               rtol=2e-2, atol=2e-2)


def test_swa_rolling_cache_bounded():
    """Sliding-window cache holds only `window` slots but matches forward."""
    cfg = reduced(get_arch("h2o-danube-3-4b"))
    assert cfg.sliding_window == 32
    caches = init_decode_caches(cfg, 2, 512)
    k_shape = jax.tree.leaves(caches)[0].shape
    assert k_shape[2] == cfg.sliding_window  # (L, B, W, kv, hd)


def test_cell_supported_matrix():
    """40 cells total: 32 runnable + 8 documented skips."""
    runnable = skips = 0
    for arch in ARCHS:
        cfg = get_arch(arch)
        for shape in SHAPES.values():
            ok, why = cell_supported(cfg, shape)
            if ok:
                runnable += 1
            else:
                skips += 1
                assert why
    assert runnable == 32 and skips == 8


def test_flash_vjp_matches_naive_attention_grads():
    """The flash-attention custom VJP (block recompute, O(S) residuals) must
    reproduce naive softmax-attention gradients exactly."""
    from repro.models.attention import blocked_attention

    def naive(q, k, v, causal, window):
        B, S, H, D = q.shape
        KV = k.shape[2]
        G = H // KV
        qg = q.reshape(B, S, KV, G, D)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) / np.sqrt(D)
        pos = jnp.arange(S)
        mask = jnp.ones((S, S), bool)
        if causal:
            mask &= pos[None, :] <= pos[:, None]
        if window:
            mask &= pos[:, None] - pos[None, :] < window
        s = jnp.where(mask[None, None, None], s.astype(jnp.float32), -1e30)
        p = jax.nn.softmax(s, -1)
        o = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(v.dtype), v)
        return o.transpose(0, 3, 1, 2, 4).reshape(B, S, H, -1)

    key = jax.random.PRNGKey(0)
    for causal, window, (B, S, H, KV, D) in [
            (True, 0, (2, 64, 4, 2, 16)), (True, 24, (2, 96, 4, 4, 8)),
            (False, 0, (1, 48, 2, 2, 8))]:
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (B, S, H, D))
        k = jax.random.normal(ks[1], (B, S, KV, D))
        v = jax.random.normal(ks[2], (B, S, KV, D))
        f1 = lambda *a: jnp.sum(jnp.sin(blocked_attention(
            *a, causal=causal, window=window, kv_block=32)))
        f2 = lambda *a: jnp.sum(jnp.sin(naive(*a, causal, window)))
        g1 = jax.grad(f1, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(f2, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


def _mha_inputs(B=1, S=256, H=2, D=64, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (B, S, H, D), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_pallas_attention_matches_naive_and_flash(causal):
    """The Pallas flash kernel (interpret mode; blocks of 128 at S 256) gives
    the output and q/k/v gradients of plain softmax attention and of the
    online-softmax scan that it replaces on the chip."""
    from repro.models.attention import _flash, _splash

    q, k, v = _mha_inputs()
    scale = 1.0 / np.sqrt(q.shape[-1])
    paths = {
        "pallas": lambda q, k, v: _splash(q, k, v, causal, scale,
                                          interpret=True),
        "naive": lambda q, k, v: jax.nn.dot_product_attention(
            q, k, v, is_causal=causal),
        "flash": lambda q, k, v: _flash(q, k, v, causal, 0, 0, 128, scale,
                                        False),
    }
    outs = {}
    for name, f in paths.items():
        loss = lambda *a: jnp.sum(jnp.sin(f(*a)))
        outs[name] = (f(q, k, v),
                      *jax.grad(loss, argnums=(0, 1, 2))(q, k, v))
    for ref in ("naive", "flash"):
        for a, b in zip(outs["pallas"], outs[ref]):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)


# (B, Sq, H, Dk), (B, Sk, KV, Dv), window, q_offset, mesh, backend -> path
_DISPATCH = {
    "mha": ((1, 256, 2, 64), (1, 256, 2, 64), 0, 0, False, "tpu", "pallas"),
    "cpu": ((1, 256, 2, 64), (1, 256, 2, 64), 0, 0, False, "cpu", "blocked"),
    "gqa": ((1, 256, 4, 64), (1, 256, 2, 64), 0, 0, False, "tpu", "blocked"),
    "window": ((1, 256, 2, 64), (1, 256, 2, 64), 64, 0, False, "tpu",
               "blocked"),
    "mla": ((1, 256, 2, 96), (1, 256, 2, 64), 0, 0, False, "tpu", "blocked"),
    "q_offset": ((1, 256, 2, 64), (1, 256, 2, 64), 0, 128, False, "tpu",
                 "blocked"),
    "seq_not_128": ((1, 200, 2, 64), (1, 200, 2, 64), 0, 0, False, "tpu",
                    "blocked"),
    "mesh": ((1, 256, 2, 64), (1, 256, 2, 64), 0, 0, True, "tpu", "blocked"),
}


@pytest.mark.parametrize("case", list(_DISPATCH))
def test_attention_dispatch_rule_and_path_counter(case, monkeypatch):
    """Only an unsharded MHA call on a TPU with no window or query offset,
    Dk == Dv and Sq == Sk a multiple of 128 takes the kernel; the path counter
    records which path each traced call took."""
    from jax.sharding import Mesh

    from repro.models import attention

    q_shape, v_shape, window, q_offset, sharded, backend, path = \
        _DISPATCH[case]
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",)) if sharded else None
    q = jax.ShapeDtypeStruct(q_shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct(v_shape[:3] + q_shape[3:], jnp.bfloat16)
    v = jax.ShapeDtypeStruct(v_shape, jnp.bfloat16)
    before = dict(attention.PATH_COUNTS)
    out = jax.eval_shape(lambda q, k, v: attention.blocked_attention(
        q, k, v, causal=True, window=window, q_offset=q_offset, kv_block=128,
        mesh=mesh), q, k, v)
    assert out.shape == q_shape[:3] + v_shape[3:]
    counted = {p: attention.PATH_COUNTS[p] - before.get(p, 0)
               for p in ("pallas", "blocked")}
    assert counted == {p: int(p == path) for p in ("pallas", "blocked")}
