"""Model-component unit tests: attention paths agree, MoE conservation,
chunked scans are chunk-size invariant, caches, rope, norms."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import attention as attn
from repro.models import cache as cache_mod
from repro.models import layers, mamba, moe, rwkv
from repro.models.config import FFN, LayerSpec, Mixer, ModelConfig

KEY = jax.random.PRNGKey(3)


def test_chunked_attention_matches_plain():
    B, S, H, KV, hd = 2, 200, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S)
    for kwargs in [dict(causal=True), dict(causal=True, window=32),
                   dict(causal=False), dict(causal=True, cap=20.0)]:
        plain = attn.plain_attention(q, k, v, pos, pos,
                                     window=kwargs.get("window"),
                                     causal=kwargs.get("causal", True),
                                     cap=kwargs.get("cap"))
        chunked = attn.chunked_attention(q, k, v, pos, pos,
                                         window=kwargs.get("window"),
                                         causal=kwargs.get("causal", True),
                                         cap=kwargs.get("cap"),
                                         q_chunk=64, k_chunk=64)
        np.testing.assert_allclose(np.asarray(plain), np.asarray(chunked),
                                   rtol=1e-4, atol=1e-4)


def test_rope_preserves_norm_and_relativity():
    B, S, H, hd = 1, 16, 2, 64
    x = jax.random.normal(KEY, (B, S, H, hd))
    pos = jnp.arange(S)
    r = layers.apply_rope(x, pos, 10_000.0)
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(x), axis=-1),
        np.linalg.norm(np.asarray(r), axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i - j
    q = jax.random.normal(jax.random.PRNGKey(5), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(6), (1, 1, 1, hd))
    def dot_at(i, j):
        qi = layers.apply_rope(q, jnp.asarray([i]), 10_000.0)
        kj = layers.apply_rope(k, jnp.asarray([j]), 10_000.0)
        return float(jnp.sum(qi * kj))
    assert abs(dot_at(3, 1) - dot_at(7, 5)) < 1e-3


def test_rmsnorm_scale_invariance():
    x = jax.random.normal(KEY, (2, 8, 32))
    p = layers.init_rmsnorm(32)
    y1 = layers.rmsnorm(p, x)
    y2 = layers.rmsnorm(p, x * 100.0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-5)


def test_softcap_bounds():
    x = jnp.linspace(-1000, 1000, 101)
    y = layers.softcap(x, 50.0)
    assert float(jnp.max(jnp.abs(y))) <= 50.0
    np.testing.assert_allclose(np.asarray(layers.softcap(x, None)),
                               np.asarray(x))


def _moe_cfg():
    return ModelConfig(name="m", n_layers=1, d_model=32, n_heads=2,
                       n_kv_heads=2, d_ff=64, vocab_size=64,
                       pattern=(LayerSpec(Mixer.ATTENTION, FFN.SWIGLU,
                                          moe=True),),
                       n_experts=4, top_k=2, dtype="float32")


def test_moe_matches_dense_expert_sum_at_high_capacity():
    """The grouped (sorted, ragged) evaluation must equal the dense
    (all-experts) weighted computation: no capacity, nothing dropped."""
    cfg = _moe_cfg()
    p = moe.init_moe(KEY, cfg, FFN.SWIGLU, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 16, cfg.d_model))
    y, aux, load = moe.apply_moe(p, cfg, FFN.SWIGLU, x)
    assert int(load.sum()) == 2 * 16 * cfg.top_k
    # dense reference
    T = 2 * 16
    xt = x.reshape(T, cfg.d_model)
    gates = jax.nn.softmax((xt @ p["router"]).astype(jnp.float32), -1)
    top_w, top_e = jax.lax.top_k(gates, cfg.top_k)
    top_w = top_w / top_w.sum(-1, keepdims=True)
    expert_out = jnp.stack([
        layers.apply_ffn(jax.tree_util.tree_map(lambda w: w[e],
                                                p["experts"]),
                         cfg, FFN.SWIGLU, xt)
        for e in range(cfg.n_experts)], axis=1)        # [T, E, d]
    ref = jnp.zeros_like(xt)
    for kk in range(cfg.top_k):
        ref += top_w[:, kk:kk + 1] * jnp.take_along_axis(
            expert_out, top_e[:, kk][:, None, None].repeat(
                cfg.d_model, axis=2), axis=1)[:, 0]
    np.testing.assert_allclose(np.asarray(y.reshape(T, -1)),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)
    assert float(aux) >= 0


def test_moe_capacity_drops_bounded():
    """Dropless under the most skewed routing: a router that sends every
    token to experts 0 and 1 loads them with all 32 tokens (GShard at
    capacity factor 1.25 would keep 20 each), and every token still gets
    both experts' outputs, as the dense evaluation gives them."""
    cfg = _moe_cfg()
    p = moe.init_moe(KEY, cfg, FFN.SWIGLU, jnp.float32)
    x = jnp.abs(jax.random.normal(KEY, (2, 16, cfg.d_model)))
    p["router"] = jnp.zeros_like(p["router"]).at[:, :2].set(1.0)
    y, _, load = moe.apply_moe(p, cfg, FFN.SWIGLU, x)
    np.testing.assert_array_equal(np.asarray(load), [32, 32, 0, 0])
    y_dense, _, _ = moe.apply_moe(p, cfg, FFN.SWIGLU,
                                  x.reshape(32, 1, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y.reshape(32, -1)),
                               np.asarray(y_dense.reshape(32, -1)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunks", [(8, 64), (16, 32)])
def test_mamba_scan_chunk_invariance(chunks):
    cfg = ModelConfig(name="mm", n_layers=1, d_model=64, n_heads=0,
                      n_kv_heads=0, d_ff=128, vocab_size=64,
                      pattern=(LayerSpec(Mixer.MAMBA, FFN.SWIGLU),),
                      dtype="float32")
    B, S, di, ds = 2, 96, cfg.mamba_d_inner, cfg.mamba_d_state
    ks = jax.random.split(KEY, 4)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (B, S, di)))
    Bm = jax.random.normal(ks[1], (B, S, ds))
    Cm = jax.random.normal(ks[2], (B, S, ds))
    x = jax.random.normal(ks[3], (B, S, di))
    A = -jnp.exp(jax.random.normal(KEY, (di, ds)) * 0.3)
    D = jnp.ones((di,))
    y1, h1 = mamba.ssm_scan(dt, Bm, Cm, x, A, D, None, chunk=chunks[0])
    y2, h2 = mamba.ssm_scan(dt, Bm, Cm, x, A, D, None, chunk=chunks[1])
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=1e-4,
                               atol=1e-4)


def test_rwkv_scan_chunk_invariance():
    B, S, H, N = 2, 96, 2, 32
    ks = jax.random.split(KEY, 5)
    r = jax.random.normal(ks[0], (B, S, H, N))
    k = jax.random.normal(ks[1], (B, S, H, N))
    v = jax.random.normal(ks[2], (B, S, H, N))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, S, H, N)) * 0.3 - 1)
    u = jax.random.normal(ks[4], (H, N)) * 0.5
    y1, s1 = rwkv.rwkv_scan(r, k, v, logw, u, None, chunk=16)
    y2, s2 = rwkv.rwkv_scan(r, k, v, logw, u, None, chunk=48)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-3,
                               atol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=2e-3,
                               atol=2e-3)


def test_ring_buffer_positions():
    # full cache
    k_pos, valid = cache_mod.ring_slot_positions(8, None, jnp.asarray(5))
    np.testing.assert_array_equal(np.asarray(k_pos), np.arange(8))
    np.testing.assert_array_equal(np.asarray(valid),
                                  np.arange(8) <= 5)
    # ring cache W=4 at pos=6: slots hold positions [4, 5, 6, 3]
    k_pos, valid = cache_mod.ring_slot_positions(4, 4, jnp.asarray(6))
    np.testing.assert_array_equal(np.asarray(k_pos), [4, 5, 6, 3])
    assert bool(valid.all())


def test_ring_buffer_positions_per_slot():
    """Vector pos -> per-row [B, L] positions/validity, row i equal to
    the scalar call at pos[i] (the batched wave-decode contract)."""
    pos = jnp.asarray([2, 6, 0])
    for window, L in ((None, 8), (4, 4)):
        k_pos, valid = cache_mod.ring_slot_positions(L, window, pos)
        assert k_pos.shape == valid.shape == (3, L)
        for i, p in enumerate([2, 6, 0]):
            kp_i, v_i = cache_mod.ring_slot_positions(L, window,
                                                      jnp.asarray(p))
            np.testing.assert_array_equal(np.asarray(k_pos[i]),
                                          np.asarray(kp_i))
            np.testing.assert_array_equal(np.asarray(valid[i]),
                                          np.asarray(v_i))


def test_write_kv_per_row_positions():
    """Batched write_kv: row i writes at its own slot (ring and full)."""
    B, L, KV, hd = 3, 4, 1, 2
    ks = jax.random.split(KEY, 2)
    k_new = jax.random.normal(ks[0], (B, 1, KV, hd))
    v_new = jax.random.normal(ks[1], (B, 1, KV, hd))
    pos = jnp.asarray([1, 6, 3])
    for window in (4, None):
        ck = jnp.zeros((B, L, KV, hd))
        cv = jnp.zeros((B, L, KV, hd))
        ck, cv = cache_mod.write_kv(ck, cv, k_new, v_new, pos, window)
        for i, p in enumerate([1, 6, 3]):
            ck_i, cv_i = cache_mod.write_kv(
                jnp.zeros((1, L, KV, hd)), jnp.zeros((1, L, KV, hd)),
                k_new[i:i + 1], v_new[i:i + 1], jnp.asarray(p), window)
            np.testing.assert_array_equal(np.asarray(ck[i]),
                                          np.asarray(ck_i[0]))
            np.testing.assert_array_equal(np.asarray(cv[i]),
                                          np.asarray(cv_i[0]))


@pytest.mark.parametrize("window", [None, 4])
def test_write_kv_chunk_at_per_row_positions(window):
    """Chunked write_kv (Sq > 1): a C-token chunk at per-row start
    positions equals C sequential single-token writes — including ring
    wraparound within a chunk and a partial validity mask."""
    B, L, C, KV, hd = 3, 4, 6, 1, 2
    ks = jax.random.split(KEY, 2)
    k_new = jax.random.normal(ks[0], (B, C, KV, hd))
    v_new = jax.random.normal(ks[1], (B, C, KV, hd))
    pos = np.array([0, 3, 5])
    n_valid = np.array([6, 4, 2])
    valid = jnp.asarray(np.arange(C)[None] < n_valid[:, None])
    ck = jax.random.normal(jax.random.PRNGKey(9), (B, L, KV, hd))
    cv = jax.random.normal(jax.random.PRNGKey(10), (B, L, KV, hd))
    got_k, got_v = cache_mod.write_kv(ck, cv, k_new, v_new,
                                      jnp.asarray(pos), window, valid=valid)
    # reference: token-by-token writes per row (ring: sequential, last
    # wins; full cache: first past-the-end token holds the final slot —
    # the keep-first-L prefill truncation)
    want_k, want_v = np.array(ck), np.array(cv)
    for b in range(B):
        wrote_end = False
        for j in range(int(n_valid[b])):
            p = int(pos[b]) + j
            if window is not None:
                slot = p % L
            else:
                slot = min(p, L - 1)
                if p >= L - 1:
                    if wrote_end:
                        continue
                    wrote_end = True
            want_k[b, slot] = np.asarray(k_new[b, j])
            want_v[b, slot] = np.asarray(v_new[b, j])
    np.testing.assert_array_equal(np.asarray(got_k), want_k)
    np.testing.assert_array_equal(np.asarray(got_v), want_v)


def test_prefill_kv_is_write_kv_chunk():
    """The one-shot prefill layout is the position-0 chunk write: full
    caches keep the first L tokens, ring caches the last L."""
    B, S, KV, hd = 2, 7, 1, 2
    k = jax.random.normal(KEY, (B, S, KV, hd))
    v = jax.random.normal(jax.random.PRNGKey(5), (B, S, KV, hd))
    for window, L in ((None, 5), (4, 4), (None, 9)):
        ck, cv = cache_mod.prefill_kv(jnp.zeros((B, L, KV, hd)),
                                      jnp.zeros((B, L, KV, hd)), k, v,
                                      window)
        if window is None:
            n = min(S, L)
            np.testing.assert_array_equal(np.asarray(ck[:, :n]),
                                          np.asarray(k[:, :n]))
            np.testing.assert_array_equal(np.asarray(cv[:, :n]),
                                          np.asarray(v[:, :n]))
        else:
            for p in range(max(S - L, 0), S):
                np.testing.assert_array_equal(np.asarray(ck[:, p % L]),
                                              np.asarray(k[:, p]))
                np.testing.assert_array_equal(np.asarray(cv[:, p % L]),
                                              np.asarray(v[:, p]))


@pytest.mark.parametrize("window", [None, 4])
@pytest.mark.parametrize("pos", [2, 9, [1, 6, 3]],
                         ids=["scalar", "scalar_past_end", "per_row"])
def test_write_token_kv_matches_write_kv(window, pos):
    """The in-place stacked write leaves repeat `layer` exactly as
    ``write_kv`` leaves that layer's own cache, and every other repeat
    untouched — scalar and per-row positions, ring wrap and full-cache
    clamp (pos 9 > L)."""
    R, B, L, KV, hd, layer = 3, 3, 4, 2, 2, 1
    ks = jax.random.split(KEY, 4)
    ck = jax.random.normal(ks[0], (R, B, L, KV, hd))
    cv = jax.random.normal(ks[1], (R, B, L, KV, hd))
    k_new = jax.random.normal(ks[2], (B, 1, KV, hd))
    v_new = jax.random.normal(ks[3], (B, 1, KV, hd))
    pos = jnp.asarray(pos)
    got_k, got_v = jax.jit(cache_mod.write_token_kv, static_argnums=6)(
        ck, cv, jnp.asarray(layer), k_new, v_new, pos, window)
    want_k, want_v = cache_mod.write_kv(ck[layer], cv[layer], k_new, v_new,
                                        pos, window)
    np.testing.assert_array_equal(np.asarray(got_k),
                                  np.asarray(ck.at[layer].set(want_k)))
    np.testing.assert_array_equal(np.asarray(got_v),
                                  np.asarray(cv.at[layer].set(want_v)))


@pytest.mark.parametrize("pos", [5, [3, 7]],
                         ids=["scalar_pos", "per_row_pos"])
def test_decode_layer_scan_carries_kv_cache(pos):
    """decode_step's layer scan carries the [R, B, L, KV, hd] k/v stacks
    and writes each token into them in place: no cache leaf enters the
    scan as a per-layer slice (xs) or leaves it as a restacked per-layer
    output (ys), which would copy every layer's cache twice a step."""
    from repro.models import transformer as T
    cfg = ModelConfig(name="scan-kv", n_layers=3, d_model=32, n_heads=4,
                      n_kv_heads=2, head_dim=8, d_ff=64, vocab_size=64,
                      dtype="float32")
    B, L = 2, 12
    params = T.init_params(KEY, cfg)
    cache = cache_mod.init_cache(cfg, B, L, dtype=jnp.float32)
    cache["pos"] = jnp.asarray(pos, jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p, t, c: T.decode_step(p, cfg, t, c))(
        params, jnp.zeros((B, 1), jnp.int32), cache).jaxpr
    scans = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 3
    eqn = scans[0]
    n_consts, n_carry = eqn.params["num_consts"], eqn.params["num_carry"]

    def shapes(vs):
        return [tuple(v.aval.shape) for v in vs]

    kv_shape = (3, B, L, cfg.n_kv_heads, cfg.resolved_head_dim)
    carry = shapes(eqn.invars[n_consts:n_consts + n_carry])
    xs = shapes(eqn.invars[n_consts + n_carry:])
    ys = shapes(eqn.outvars[n_carry:])
    assert carry.count(kv_shape) == 2          # the k and v stacks
    assert not any(s[1:] == kv_shape[1:] for s in xs + ys), (xs, ys)


@pytest.mark.parametrize("window,cap,KV", [
    (None, None, 2), (4, None, 2), (None, 30.0, 1), (6, 20.0, 4),
])
def test_sq1_flash_decode_dispatch_matches_jnp(window, cap, KV):
    """Sq == 1 under the pallas impl routes to the flash-decode kernel
    and agrees with the jnp reference path — including per-slot ragged
    positions, ring-window validity, GQA and softcap."""
    B, L, H, hd = 3, 8, 4, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, L, KV, hd))
    v = jax.random.normal(ks[2], (B, L, KV, hd))
    pos = jnp.asarray([2, 7, 5])
    q_pos = pos[:, None]
    k_pos, valid = cache_mod.ring_slot_positions(L, window, pos)
    kwargs = dict(causal=True, window=window, cap=cap, k_valid=valid)
    ref = attn.multihead_attention(q, k, v, q_pos, k_pos,
                                   force_impl="jnp", **kwargs)
    out = attn.multihead_attention(q, k, v, q_pos, k_pos,
                                   force_impl="pallas", **kwargs)
    assert out.shape == (B, 1, H, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_sq1_dispatch_invokes_decode_kernel(monkeypatch):
    """The Sq == 1 pallas dispatch must hit kernels.decode_attention,
    not the flash (prefill) kernel."""
    from repro.kernels import ops as kernel_ops
    calls = []
    real = kernel_ops.flash_decode_attention
    monkeypatch.setattr(
        kernel_ops, "flash_decode_attention",
        lambda *a, **kw: calls.append("decode") or real(*a, **kw))
    monkeypatch.setattr(
        kernel_ops, "flash_attention",
        lambda *a, **kw: calls.append("flash"))
    B, L, H, hd = 2, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (B, L, 2, hd))
    v = jax.random.normal(ks[2], (B, L, 2, hd))
    k_pos, valid = cache_mod.ring_slot_positions(L, None, jnp.asarray(3))
    out = attn.multihead_attention(q, k, v, jnp.full((1,), 3), k_pos,
                                   force_impl="pallas", k_valid=valid)
    assert calls == ["decode"] and out.shape == (B, 1, H, hd)


def test_effective_window_long_mode():
    cfg = ModelConfig(name="g", n_layers=2, d_model=64, n_heads=2,
                      n_kv_heads=2, d_ff=128, vocab_size=64,
                      pattern=(LayerSpec(window=16), LayerSpec(window=None)),
                      long_mode_window=32, dtype="float32")
    local, glob = cfg.pattern
    assert cache_mod.effective_window(cfg, local, False) == 16
    assert cache_mod.effective_window(cfg, glob, False) is None
    assert cache_mod.effective_window(cfg, glob, True) == 32
    assert cfg.supports_long_context()


def test_moe_dense_decode_matches_dispatch():
    """The S=1 dense-decode path must agree with the grouped dispatch."""
    import jax
    import jax.numpy as jnp
    cfg = _moe_cfg()
    p = moe.init_moe(jax.random.PRNGKey(2), cfg, FFN.SWIGLU, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(3), (8, 1, cfg.d_model))
    y_dense, _, _ = moe.apply_moe(p, cfg, FFN.SWIGLU, x)
    # grouped path on the same tokens laid out as one row of S=8
    y_grouped, _, _ = moe.apply_moe(p, cfg, FFN.SWIGLU,
                                    x.reshape(1, 8, cfg.d_model))
    np.testing.assert_allclose(np.asarray(y_dense.reshape(8, -1)),
                               np.asarray(y_grouped.reshape(8, -1)),
                               rtol=2e-4, atol=2e-4)
