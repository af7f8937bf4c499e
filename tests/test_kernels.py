"""Per-kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ecoscan import ecoscan, route_and_scan
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_prefill import flash_prefill


def k(i):
    return jax.random.PRNGKey(i)


@pytest.mark.parametrize("B,d,NC,CAP,P,K", [
    (2, 32, 8, 64, 2, 5),
    (4, 128, 16, 128, 4, 10),
    (1, 64, 5, 96, 5, 8),
])
def test_ecoscan_sweep(B, d, NC, CAP, P, K):
    q = jax.random.normal(k(0), (B, d))
    data = jax.random.normal(k(1), (NC, CAP, d))
    lens = jax.random.randint(k(2), (NC,), CAP // 2, CAP + 1)
    probes = jnp.stack([jax.random.permutation(k(3 + i), NC)[:P]
                        for i in range(B)]).astype(jnp.int32)
    dk, ik = ecoscan(q, data, lens, probes, k=K)
    dr, ir = ref.ecoscan(q, data, lens, probes, K)
    np.testing.assert_allclose(dk, dr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(ik) == np.asarray(ir)).all()


@pytest.mark.parametrize("B", [1, 8])
@pytest.mark.parametrize("probe_tile", [1, 2, 3, 4])
def test_ecoscan_merge_and_tiling_sweep(B, probe_tile):
    """The top-k merge under every probe tiling (including tiles that
    don't divide P), for one query and a batch, must match the reference
    exactly."""
    d, NC, CAP, P, K = 48, 9, 64, 5, 8
    q = jax.random.normal(k(0), (B, d))
    data = jax.random.normal(k(1), (NC, CAP, d))
    lens = jax.random.randint(k(2), (NC,), 1, CAP + 1)
    probes = jnp.stack([jax.random.permutation(k(3 + i), NC)[:P]
                        for i in range(B)]).astype(jnp.int32)
    dk, ik = ecoscan(q, data, lens, probes, k=K, probe_tile=probe_tile)
    dr, ir = ref.ecoscan(q, data, lens, probes, K)
    np.testing.assert_allclose(dk, dr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(ik) == np.asarray(ir)).all()


@pytest.mark.parametrize("B", [1, 8])
def test_ecoscan_exhausted_candidates_emit_sentinels(B):
    """Fewer than k valid candidates across multiple grid steps must pad
    with id -1, never duplicate an already-selected id (regression for a
    merge re-picking stale slots)."""
    q = jnp.zeros((B, 16))
    data = jnp.zeros((4, 32, 16))
    lens = jnp.asarray([3, 0, 0, 0], jnp.int32)
    probes = jnp.tile(jnp.asarray([[0, 1]], jnp.int32), (B, 1))
    _, ik = ecoscan(q, data, lens, probes, k=6, probe_tile=1)
    for row in np.asarray(ik):
        assert sorted(row[:3]) == [0, 1, 2]
        assert (row[3:] == -1).all()


def test_ecoscan_empty_clusters():
    """Probing only empty clusters yields all-sentinel output."""
    q = jax.random.normal(k(0), (2, 16))
    data = jax.random.normal(k(1), (4, 32, 16))
    lens = jnp.asarray([0, 5, 0, 0], jnp.int32)
    probes = jnp.asarray([[0, 2], [2, 3]], jnp.int32)
    dk, ik = ecoscan(q, data, lens, probes, k=4)
    dr, ir = ref.ecoscan(q, data, lens, probes, 4)
    assert (np.asarray(ik) == -1).all()
    assert (np.asarray(ir) == -1).all()
    np.testing.assert_allclose(dk, dr)


def test_ecoscan_all_padded_probes():
    """Probe ids < 0 are padding and contribute no candidates."""
    q = jax.random.normal(k(0), (2, 16))
    data = jax.random.normal(k(1), (4, 32, 16))
    lens = jnp.full((4,), 32, jnp.int32)
    probes = -jnp.ones((2, 3), jnp.int32)
    dk, ik = ecoscan(q, data, lens, probes, k=4)
    dr, ir = ref.ecoscan(q, data, lens, probes, 4)
    assert (np.asarray(ik) == -1).all()
    assert (np.asarray(ir) == -1).all()
    # ...and a mix of real + padded probes matches the real-only result
    probes_mix = jnp.asarray([[1, -1, 2], [0, 3, -1]], jnp.int32)
    probes_real = jnp.asarray([[1, 2], [0, 3]], jnp.int32)
    dm, im = ecoscan(q, data, lens, probes_mix, k=4)
    dr2, ir2 = ecoscan(q, data, lens, probes_real, k=4)
    np.testing.assert_allclose(dm, dr2, rtol=2e-5, atol=2e-5)
    assert (np.asarray(im) == np.asarray(ir2)).all()


def test_ecoscan_duplicate_probes():
    """A cluster probed twice must match the reference (duplicates are
    surfaced identically by kernel and oracle)."""
    q = jax.random.normal(k(0), (2, 16))
    data = jax.random.normal(k(1), (4, 32, 16))
    lens = jnp.full((4,), 32, jnp.int32)
    probes = jnp.asarray([[1, 1, 2], [3, 0, 3]], jnp.int32)
    dk, ik = ecoscan(q, data, lens, probes, k=6)
    dr, ir = ref.ecoscan(q, data, lens, probes, 6)
    np.testing.assert_allclose(dk, dr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(ik) == np.asarray(ir)).all()


@pytest.mark.parametrize("n_probe", [1, 3, 8])
def test_route_and_scan_fused_matches_ref(n_probe):
    """The single-call fused route->scan equals routing + scan done by the
    pure-jnp oracle."""
    B, d, NC, CAP, K = 4, 32, 8, 64, 7
    q = jax.random.normal(k(0), (B, d))
    cent = jax.random.normal(k(1), (NC, d))
    data = jax.random.normal(k(2), (NC, CAP, d))
    lens = jax.random.randint(k(3), (NC,), 1, CAP + 1)
    dk, sk, pk = route_and_scan(q, cent, data, lens, n_probe=n_probe, k=K)
    dr, sr, pr = ref.route_and_scan(q, cent, data, lens, n_probe, K)
    assert (np.asarray(pk) == np.asarray(pr)).all()
    np.testing.assert_allclose(dk, dr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(sk) == np.asarray(sr)).all()


def test_ecoscan_respects_lens():
    """Slots beyond the cluster's valid count must never be returned."""
    q = jnp.zeros((1, 16))
    data = jnp.zeros((2, 32, 16))  # all points identical (dist 0)
    lens = jnp.asarray([4, 0], jnp.int32)
    probes = jnp.asarray([[0, 1]], jnp.int32)
    _, ids = ecoscan(q, data, lens, probes, k=6)
    valid = np.asarray(ids)[0]
    assert set(valid[valid >= 0]) <= {0, 1, 2, 3}


@pytest.mark.parametrize("N,d,NC", [(100, 16, 5), (513, 64, 33),
                                    (1024, 128, 64)])
def test_kmeans_assign_sweep(N, d, NC):
    x = jax.random.normal(k(0), (N, d))
    c = jax.random.normal(k(1), (NC, d))
    a1, d1 = ops.kmeans_assign(x, c)
    a2, d2 = ref.kmeans_assign(x, c)
    assert (np.asarray(a1) == np.asarray(a2)).all()
    np.testing.assert_allclose(d1, d2, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,NW,d", [(1, 5, 32), (3, 200, 64), (2, 257, 128)])
def test_scr_score_sweep(B, NW, d):
    w = jax.random.normal(k(0), (B, NW, d))
    q = jax.random.normal(k(1), (B, d))
    np.testing.assert_allclose(ops.scr_score(w, q), ref.scr_score(w, q),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,d,ND,CAPW,K", [
    (1, 32, 6, 8, 3),
    (3, 64, 12, 24, 5),
    (2, 128, 40, 17, 9),
])
def test_scr_select_sweep(B, d, ND, CAPW, K):
    from repro.kernels.scr_select import scr_select
    q = jax.random.normal(k(0), (B, d))
    data = jax.random.normal(k(1), (ND, CAPW, d))
    lens = jax.random.randint(k(2), (ND,), 0, CAPW + 1)
    ids = jax.random.randint(k(3), (B, K), 0, ND).astype(jnp.int32)
    sk, wk = scr_select(q, data, lens, ids)
    sr, wr = ref.scr_select(q, data, lens, ids)
    np.testing.assert_allclose(sk, sr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(wk) == np.asarray(wr)).all()


@pytest.mark.parametrize("doc_tile", [1, 2, 3, 8])
def test_scr_select_doc_tiling_sweep(doc_tile):
    """Every doc tiling (including tiles that don't divide K) must match
    the reference exactly."""
    from repro.kernels.scr_select import scr_select
    B, d, ND, CAPW, K = 3, 48, 9, 16, 5
    q = jax.random.normal(k(0), (B, d))
    data = jax.random.normal(k(1), (ND, CAPW, d))
    lens = jax.random.randint(k(2), (ND,), 0, CAPW + 1)
    ids = jax.random.randint(k(3), (B, K), 0, ND).astype(jnp.int32)
    sk, wk = scr_select(q, data, lens, ids, doc_tile=doc_tile)
    sr, wr = ref.scr_select(q, data, lens, ids)
    np.testing.assert_allclose(sk, sr, rtol=2e-5, atol=2e-5)
    assert (np.asarray(wk) == np.asarray(wr)).all()


def test_scr_select_padded_and_windowless_docs():
    """Padded slots (id -1) and zero-window docs emit the (-NEG, -1)
    sentinel pair; real docs are unaffected by padding neighbours."""
    from repro.kernels.ref import NEG
    q = jax.random.normal(k(0), (2, 16))
    data = jax.random.normal(k(1), (4, 8, 16))
    lens = jnp.asarray([3, 0, 8, 1], jnp.int32)
    ids = jnp.asarray([[0, 1, -1], [2, 3, 1]], jnp.int32)
    s, w = ops.scr_select(q, data, lens, ids)
    s, w = np.asarray(s), np.asarray(w)
    assert w[0, 1] == -1 and w[0, 2] == -1 and w[1, 2] == -1
    assert s[0, 1] == -NEG and s[0, 2] == -NEG
    assert w[0, 0] >= 0 and w[1, 0] >= 0 and w[1, 1] == 0
    # windows beyond lens are never selected
    assert w[0, 0] < 3 and w[1, 1] < 1


def test_scr_select_host_vs_device_agreement():
    """use_pallas=True (kernel) and use_pallas=False (pure-jnp oracle)
    agree on scores and picked windows — the dispatch contract the
    batched SCR path relies on."""
    q = jax.random.normal(k(4), (4, 32))
    data = jax.random.normal(k(5), (10, 12, 32))
    lens = jax.random.randint(k(6), (10,), 0, 13)
    ids = jax.random.randint(k(7), (4, 6), -1, 10).astype(jnp.int32)
    sd, wd = ops.scr_select(q, data, lens, ids, use_pallas=True)
    sh, wh = ops.scr_select(q, data, lens, ids, use_pallas=False)
    np.testing.assert_allclose(sd, sh, rtol=2e-5, atol=2e-5)
    assert (np.asarray(wd) == np.asarray(wh)).all()


def test_scr_select_first_max_tie_break():
    """Duplicate best windows resolve to the lowest window id, matching
    the host Python max() scan."""
    d = 8
    q = jnp.ones((1, d))
    w = jnp.ones((d,))
    data = jnp.stack([jnp.stack([w * 0.5, w, w, w * 0.2])])  # [1, 4, d]
    lens = jnp.asarray([4], jnp.int32)
    ids = jnp.asarray([[0]], jnp.int32)
    _, wins = ops.scr_select(q, data, lens, ids)
    assert int(np.asarray(wins)[0, 0]) == 1


@pytest.mark.parametrize("B,M,N", [(1, 4, 100), (2, 8, 513), (3, 16, 64)])
def test_pq_adc_sweep(B, M, N):
    lut = jax.random.normal(k(0), (B, M, 256))
    codes = jax.random.randint(k(1), (N, M), 0, 256).astype(jnp.uint8)
    np.testing.assert_allclose(ops.pq_adc(lut, codes), ref.pq_adc(lut, codes),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("B,H,G,dh,S,kvlen", [
    (1, 4, 1, 32, 128, 100),
    (2, 8, 2, 64, 700, 650),
    (2, 16, 16, 64, 512, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, H, G, dh, S, kvlen, dtype):
    q = jax.random.normal(k(0), (B, H, dh), dtype)
    kk = jax.random.normal(k(1), (B, S, G, dh), dtype)
    vv = jax.random.normal(k(2), (B, S, G, dh), dtype)
    o1 = decode_attention(q, kk, vv, kvlen)
    o2 = ref.decode_attention(q, kk, vv, kvlen)
    tol = 2e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_decode_attention_per_slot_lengths():
    """Slot-paged batches: kv_len is a per-row [B] vector — every row is
    masked to its OWN length, matching per-row calls of the oracle."""
    B, H, G, dh, S = 4, 8, 2, 32, 128
    q = jax.random.normal(k(0), (B, H, dh))
    kk = jax.random.normal(k(1), (B, S, G, dh))
    vv = jax.random.normal(k(2), (B, S, G, dh))
    lens = jnp.asarray([3, 100, 128, 57], jnp.int32)
    o1 = decode_attention(q, kk, vv, lens)
    for b in range(B):
        row = ref.decode_attention(q[b:b + 1], kk[b:b + 1], vv[b:b + 1],
                                   int(lens[b]))
        np.testing.assert_allclose(np.asarray(o1[b:b + 1], np.float32),
                                   np.asarray(row, np.float32),
                                   rtol=2e-4, atol=2e-4)


def test_decode_attention_ring_clamps_per_slot():
    """Ring pages: a slot whose absolute position exceeds the ring size
    attends ALL S filled slots (mask length min(kv_len, S)), while a
    co-resident still inside the ring keeps its shorter mask. Kernel and
    oracle agree, and ring=True differs from the unclamped call only via
    the clamp."""
    B, H, G, dh, S = 2, 4, 1, 32, 64
    q = jax.random.normal(k(3), (B, H, dh))
    kk = jax.random.normal(k(4), (B, S, G, dh))
    vv = jax.random.normal(k(5), (B, S, G, dh))
    lens = jnp.asarray([150, 20], jnp.int32)       # slot 0 wrapped, 1 not
    o1 = decode_attention(q, kk, vv, lens, ring=True)
    o2 = ref.decode_attention(q, kk, vv, lens, ring=True)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=2e-4, atol=2e-4)
    full = ref.decode_attention(q, kk, vv, jnp.asarray([64, 20], jnp.int32))
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(full, np.float32))


# Paged decode cases: heads, KV heads, head width, page size, table width,
# dtype, each row's absolute position (its new token's), the ring length
# (0: none), int8 K/V, and the rows that share their first page.
_PAGED = {
    "small-f32": (4, 2, 32, 16, 4, jnp.float32, [48, 21, 31], 0, False,
                  (0, 1)),
    # the served widths: Qwen2.5-0.5B (2 KV heads) and Granite (8), 18
    # pages of 32; a row of length 1, one at full width, one whose new
    # token opens a page, two sharing a prefix page
    "qwen-bf16": (14, 2, 64, 32, 18, jnp.bfloat16, [0, 575, 40, 100, 64,
                                                    200], 0, False, (2, 3)),
    "granite-bf16": (16, 8, 64, 32, 18, jnp.bfloat16, [0, 575, 40, 100, 64,
                                                       200], 0, False,
                     (2, 3)),
    # sliding-window ring of 64: rows inside it and rows wrapped past it
    "ring-bf16": (14, 2, 64, 32, 2, jnp.bfloat16, [10, 63, 64, 150, 200],
                  64, False, ()),
    "int8": (14, 2, 64, 32, 18, jnp.bfloat16, [0, 575, 40, 100, 64, 200],
             0, True, (2, 3)),
}


@pytest.mark.parametrize("case", list(_PAGED))
def test_decode_attention_paged_matches_gather_and_ref(case):
    """Block-table decode read in place from the layer-stacked pool: the
    kernel walks each slot's live pages through its table and must match
    (a) the float32 paged oracle and (b) the path it replaced, the
    table gather (`dense._pool_gather`) plus `layers.decode_attention` /
    `decode_attention_q8` over each row's pos + 1 positions. Table
    tails past a row's pages hold page 0, as the engine leaves them."""
    from repro.models import dense, layers as L
    H, G, dh, ps, W, dtype, pos, ring, quant, shared = _PAGED[case]
    B, layer, gd = len(pos), 1, G * dh
    pos = jnp.asarray(pos, jnp.int32)
    P = 1 + B * W
    table = 1 + jnp.arange(B * W, dtype=jnp.int32).reshape(B, W)
    if shared:
        a, b = shared
        table = table.at[b, 0].set(table[a, 0])
    need = W if ring else -(-(np.asarray(pos) + 1) // ps)
    table = jnp.where(jnp.arange(W)[None] < jnp.reshape(need, (-1, 1)),
                      table, 0)
    q = jax.random.normal(k(6), (B, H, dh), dtype)
    kv = [jax.random.normal(k(7 + i), (2, P, ps, G, dh), dtype)
          for i in range(2)]
    own = [jax.random.normal(k(9 + i), (B, G, dh), dtype) for i in range(2)]
    names = ("k", "v", "k_s", "v_s") if quant else ("k", "v")
    if quant:
        (kq, ks), (vq, vs) = (L.quantize_kv(x) for x in kv)
        (nkq, nks), (nvq, nvs) = (L.quantize_kv(x) for x in own)
        kv, own = [kq, vq, ks, vs], [nkq, nvq, nks, nvs]
    # K/V pages lane-dense and padded to whole 128-lane rows, as served
    lanes = -(-gd // 128) * 128
    pool = {n: x if n.endswith("_s") else jnp.pad(
        x.reshape(x.shape[:3] + (gd,)),
        ((0, 0),) * 3 + ((0, lanes - gd),)) for n, x in zip(names, kv)}
    new = tuple(own)
    cursor = pos % ring if ring else pos
    # the step's own token is in the pool at its cursor, as served
    pg = jnp.take_along_axis(table, (cursor // ps)[:, None], axis=1)[:, 0]
    dense._pool_write(pool, layer, pg, cursor % ps, dict(zip(names, new)))
    pool = tuple(pool[n] for n in names)
    earlier = jnp.minimum(pos, ring) if ring else pos
    if not ring:   # odd rows leave out no cursor: kv_len alone bounds them
        cursor = jnp.where(jnp.arange(B) % 2 == 1, W * ps, cursor)
    o = ops.decode_attention_paged(q, new, pool, earlier, cursor, table,
                                   layer)
    o_ref = ref.decode_attention_paged(q, new, pool, earlier, cursor, table,
                                       layer)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_ref, np.float32),
                               rtol=tol, atol=tol)
    rows = [dense._pool_gather(x[layer], table, ps) for x in pool]
    rows = [x[..., :gd].reshape(x.shape[:2] + (G, dh)) if x.shape[-1] ==
            lanes else x for x in rows]
    lens = jnp.minimum(pos + 1, ring) if ring else pos + 1
    if quant:
        o_old = L.decode_attention_q8(q[:, None], rows[0], rows[2], rows[1],
                                      rows[3], lens)
    else:
        o_old = L.decode_attention(q[:, None], rows[0], rows[1], lens)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(o_old[:, 0], np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("arch", ["qwen25_0_5b", "h2o_danube_1_8b"])
def test_paged_decode_visits_the_pages_the_engine_counts(arch, monkeypatch):
    """Each decode step the kernel fetches `live_pages` of every row's
    earlier positions: summed over the slots, exactly the `pages_live`
    the engine records on its `engine/decode_step` span (decoding slots
    only; a sliding-window ring stops growing at its width). Prompts
    share a prefix, and answers cross page boundaries."""
    from repro.configs import get_reduced
    from repro.kernels.decode_attention import live_pages
    from repro.models import dense, model
    from repro.serving.engine import ContinuousEngine
    from repro.serving.trace import TraceSink

    seen = []
    served = dense.decode_attention_paged

    def spy(q, new, pool, kv_len, *rest):
        jax.debug.callback(lambda n: seen.append(np.asarray(n)), kv_len)
        return served(q, new, pool, kv_len, *rest)

    monkeypatch.setattr(dense, "decode_attention_paged", spy)
    cfg = get_reduced(arch)
    sink = TraceSink()
    ce = ContinuousEngine(cfg, model.init_params(cfg, k(0)), slots=3,
                          max_len=96, page_size=16, eos_id=-1, trace=sink)
    rng = np.random.default_rng(3)
    head = rng.integers(4, 500, 20).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(4, 500, n)]).astype(
        np.int32) for n in (13, 30)] + [rng.integers(4, 500, 70).astype(
            np.int32)]
    ce.generate(prompts, max_new=20)
    live = [r.attrs["pages_live"] for r in
            sink.query(comp="engine", name="decode_step") if r.ph == "B"]
    visited = [int(live_pages(n, ce.page_size).sum()) for n in seen]
    assert len(visited) == cfg.num_layers * len(live) > 0
    assert visited == [n for n in live for _ in range(cfg.num_layers)]


@pytest.mark.parametrize("B,H,S,dh,window", [
    (1, 2, 256, 32, None),
    (1, 2, 300, 64, 64),
    (2, 4, 128, 32, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_sweep(B, H, S, dh, window, dtype):
    q = jax.random.normal(k(0), (B, H, S, dh), dtype)
    kk = jax.random.normal(k(1), (B, H, S, dh), dtype)
    vv = jax.random.normal(k(2), (B, H, S, dh), dtype)
    o1 = flash_prefill(q, kk, vv, window=window)
    o2 = ref.flash_prefill(q, kk, vv, window=window)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               rtol=tol, atol=tol)


def test_flash_prefill_matches_model_attention():
    """Cross-check the kernel against the model's chunked-scan attention."""
    from repro.models.layers import attention
    B, H, S, dh = 1, 4, 256, 32
    q = jax.random.normal(k(0), (B, S, H, dh))
    kv = jax.random.normal(k(1), (B, S, H, dh))
    vv = jax.random.normal(k(2), (B, S, H, dh))
    o_model = attention(q, kv, vv, causal=True, chunk=64)
    o_kernel = flash_prefill(q.transpose(0, 2, 1, 3),
                             kv.transpose(0, 2, 1, 3),
                             vv.transpose(0, 2, 1, 3))
    np.testing.assert_allclose(np.asarray(o_model, np.float32),
                               np.asarray(o_kernel.transpose(0, 2, 1, 3),
                                          np.float32), rtol=2e-3, atol=2e-3)
