"""Streaming secure aggregation (ISSUE 15).

The headline is a MEMORY claim with an integer proof: masked uploads fold
one at a time into a field accumulator (peak buffered <= 2 at any cohort
size), and because the masking ring makes every sum exact, the streamed
masked total unmasks to BITWISE the buffer-all protocol's result — no FMA
tolerance anywhere.  The suite pins:

1. the ring/pack/quantize primitives (trust/secagg/stream.py),
2. the streaming fold + dropout recovery at finalize, incl. the Shamir
   threshold boundary (t+1 reveals reconstruct, t fail loudly),
3. the real 4-client Shamir protocol: stream == legacy bitwise, dropouts
   before upload / after upload (no reveal) / during finalize,
4. quantize-then-mask (qsgd8 grid in a cohort-sized ring) composing with
   the wire, and central DP landing exactly once at finalize (Pallas path),
5. the trust-pipeline gate relaxation: CDP-only pipelines stream bitwise,
   while defense/LDP/FHE/SA/LSA configurations still pin exact mode,
6. the ISSUE-15 lint satellite: the secagg modules hold zero legacy
   statement-position ``extra`` idioms (regression-pinned).
"""

import numpy as np
import pytest

from .conftest import tiny_config


def _sa_config(**kw):
    base = dict(
        client_num_in_total=4,
        client_num_per_round=4,
        comm_round=2,
        epochs=1,
        batch_size=16,
        synthetic_train_size=256,
        synthetic_test_size=64,
        training_type="cross_silo",
        enable_secagg=True,
        frequency_of_the_test=0,
        extra={"secagg_method": "shamir", "secagg_stream": True},
    )
    extra = kw.pop("extra", {})
    base.update(kw)
    merged = dict(base["extra"])
    merged.update(extra)
    base["extra"] = merged
    return tiny_config(**base)


def _run_sa(cfg, **kw):
    import fedml_tpu
    from fedml_tpu.cross_silo.secagg_shamir import run_shamir_secagg_process_group
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    return run_shamir_secagg_process_group(cfg, ds, model, timeout=120.0, **kw)


def _leaves_equal(a, b) -> bool:
    import jax

    la = jax.tree_util.tree_leaves(jax.device_get(a))
    lb = jax.tree_util.tree_leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# -- 1) primitives ------------------------------------------------------------

def test_pack_ring_roundtrip_all_widths():
    from fedml_tpu.trust.secagg import stream as st

    rng = np.random.default_rng(0)
    for bits, per_elem in ((8, 1), (11, 2), (16, 2), (23, 3), (24, 3),
                           (31, 4), (32, 4)):
        v = rng.integers(0, (1 << bits) - 1, 777, dtype=np.int64)
        packed = st.pack_ring(v, bits)
        assert packed.nbytes == 777 * per_elem, bits
        out = st.unpack_ring(packed, bits, 777)
        assert np.array_equal(v, out), bits
    with pytest.raises(ValueError):
        st.unpack_ring(st.pack_ring(rng.integers(0, 255, 10), 8), 8, 11)
    with pytest.raises(ValueError):
        st.pack_ring(rng.integers(0, 7, 4), 40)


def test_ring_sizing_and_meta():
    from fedml_tpu.trust.secagg import stream as st

    r4 = st.ring_for("qsgd8", 4, q_bits=16, q8_frac_bits=7)
    assert r4.bits == 11 and r4.wire_nbytes(1) == 2  # u16 at a 4-cohort
    r10k = st.ring_for("qsgd8", 10_000, q_bits=16, q8_frac_bits=7)
    assert r10k.bits == 23 and r10k.wire_nbytes(1) == 3  # packed 3-byte
    dense = st.ring_for(None, 10_000, q_bits=16, q8_frac_bits=7)
    assert dense.bits == 31 and dense.wire_nbytes(1) == 4  # u32, prime field
    from fedml_tpu.trust.secagg.field import DEFAULT_PRIME

    assert dense.modulus == DEFAULT_PRIME
    meta = r4.meta(100)
    assert r4.matches(meta) and not r10k.matches(meta) and not dense.matches(meta)
    # topk has no masked composition: unknown codecs are refused loudly
    with pytest.raises(ValueError):
        st.MaskedRing("topk", 4, 7)


def test_stochastic_int8_quantizer_unbiased_and_clipped():
    from fedml_tpu.trust.secagg import stream as st

    x = np.random.default_rng(1).normal(0, 0.1, 4096).astype(np.float32)
    qs = np.stack([st.quantize_stochastic_int8(x, 7, [s, 3]) for s in range(64)])
    assert qs.min() >= -127 and qs.max() <= 127
    err = np.abs(qs.mean(0) / 128.0 - np.clip(x, -127 / 128, 127 / 128))
    assert err.max() < 0.02, err.max()
    # determinism: same seed -> same draw
    assert np.array_equal(st.quantize_stochastic_int8(x, 7, [9, 9]),
                          st.quantize_stochastic_int8(x, 7, [9, 9]))
    # clipping engages on out-of-grid values
    big = np.asarray([10.0, -10.0], np.float32)
    assert np.array_equal(st.quantize_stochastic_int8(big, 7, 0),
                          np.asarray([127, -127]))


def test_field_accumulator_lazy_reduction_exact():
    from fedml_tpu.parallel.stream_fold import FieldStreamAccumulator

    p = 2**23
    acc = FieldStreamAccumulator([np.zeros(64, np.int64)], p)
    rng = np.random.default_rng(2)
    expect = np.zeros(64, np.int64)
    for _ in range(300):
        v = rng.integers(0, p, 64, dtype=np.int64)
        acc.fold_leaf(0, v)
        expect = (expect + v) % p
    assert np.array_equal(acc.host_sums()[0], expect)
    # restart from a journaled sum
    acc2 = FieldStreamAccumulator([np.zeros(64, np.int64)], p,
                                  sums=acc.host_sums())
    acc2.fold_leaf(0, np.ones(64, np.int64))
    assert np.array_equal(acc2.host_sums()[0], (expect + 1) % p)


def test_streaming_masked_sum_exact_with_dropouts():
    """Fold-one-at-a-time == batch sum, with clients dropping BEFORE upload
    (orphaned pair masks cancelled from seeds) — the integer identity."""
    from fedml_tpu.trust.secagg import stream as st

    n, d = 8, 300
    ring = st.ring_for("qsgd8", n, q_bits=16, q8_frac_bits=7)
    drop_before = {5, 7}
    q = {u: st.quantize_stochastic_int8(
        np.random.default_rng(u).normal(0, 0.05, d).astype(np.float32),
        ring.frac_bits, u) for u in range(1, n + 1)}
    self_seed = {u: 1000 + u for u in range(1, n + 1)}
    pair = {(u, v): 7000 + min(u, v) * 100 + max(u, v)
            for u in range(1, n + 1) for v in range(1, n + 1) if u != v}
    msum = st.StreamingMaskedSum(d, ring)
    for u in range(1, n + 1):
        if u in drop_before:
            continue
        peers = {v: pair[(u, v)] for v in range(1, n + 1) if v != u}
        msum.fold(st.mask_vector(np.mod(q[u], ring.modulus), u, peers,
                                 self_seed[u], ring.modulus))
    survivors = [u for u in range(1, n + 1) if u not in drop_before]
    total = msum.finalize(
        {u: self_seed[u] for u in survivors},
        {(i, j): pair[(i, j)] for i in drop_before for j in survivors})
    assert np.array_equal(total, sum(q[u] for u in survivors))
    assert msum.peak_buffered <= 2
    # a masked upload alone is field noise, not the plaintext
    assert not np.array_equal(msum.masked_total() % ring.modulus,
                              sum(q[u] for u in survivors) % ring.modulus)


def test_pallas_noise_kernel_matches_reference():
    import jax
    import jax.numpy as jnp

    from fedml_tpu.ops.pallas import noise as nz

    x = jnp.asarray(np.random.default_rng(0).normal(0, 1, 2500).astype(np.float32))
    k = jax.random.PRNGKey(7)
    out = nz.apply_gaussian_noise(x, k, 0.25, interpret=True)
    ref = nz.apply_gaussian_noise_reference(x, k, 0.25)
    assert out.shape == x.shape
    assert bool(jnp.all(out == ref))
    # sigma=0 is the identity
    assert bool(jnp.all(nz.apply_gaussian_noise(x, k, 0.0, interpret=True) == x))


# -- 2) threshold boundary ----------------------------------------------------

def test_shamir_threshold_boundary_t_plus_one_vs_t(eight_devices):
    """The hard decode bound at finalize: with exactly T+1 reveals the
    streamed round reconstructs; with T it must fail loudly (never a wrong
    silent aggregate)."""
    import fedml_tpu
    from fedml_tpu.cross_silo.secagg_shamir import (
        SAAggregator, derive_round_seed, shamir_secagg_params,
    )
    from fedml_tpu.data import loader
    from fedml_tpu.data.dataset import pad_eval_set
    from fedml_tpu.models import model_hub
    from fedml_tpu.trust.secagg import stream as st
    from fedml_tpu.trust.secagg.shamir import shamir_share

    cfg = _sa_config(run_id="sas_thr", extra={"secagg_privacy_t": 2})
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    t, _ = shamir_secagg_params(cfg)
    assert t == 2

    def build_round(n_reveals):
        agg = SAAggregator(cfg, model, ds.train_x[:16],
                           pad_eval_set(ds.test_x, ds.test_y, 32))
        assert agg.field_stream
        rng_np = np.random.RandomState(3)
        b = {u: 500 + u for u in range(1, 5)}
        shares = {u: shamir_share(b[u], 4, t + 1, rng_np) for u in b}
        for u in range(1, 5):
            xf = np.mod(np.full(agg.model_dim, u, np.int64), agg.ring.modulus)
            seed = derive_round_seed(b[u], 0)
            masked = st.mask_vector(xf, u, {}, seed, agg.ring.modulus)
            agg.add_masked_upload(u, st.pack_ring(masked, agg.ring.bits), 1.0,
                                  dict(agg.ring.meta(agg.model_dim), delta=False))
        for v in range(1, n_reveals + 1):
            agg.add_reveal(v, {str(u): shares[u][v - 1][1] for u in b}, {})
        return agg

    ok = build_round(t + 1)
    ok.aggregate(0)  # reconstructs
    assert ok.peak_buffered_updates <= 2
    short = build_round(t)
    with pytest.raises(RuntimeError, match="not enough b-shares"):
        short.aggregate(0)


# -- 3) the real protocol -----------------------------------------------------

def test_stream_dense_bitwise_vs_legacy(eight_devices):
    """Mod-field exactness: a streamed run's final global is BITWISE the
    buffer-all run's, even though each run drew fresh OS-entropy masks —
    the masks cancel exactly."""
    import jax

    h_s, srv_s = _run_sa(_sa_config(run_id="sas1"))
    h_l, srv_l = _run_sa(_sa_config(run_id="sas1l", extra={"secagg_stream": False}))
    assert len(h_s) == len(h_l) == 2
    assert srv_s.aggregator.field_stream and not srv_l.aggregator.field_stream
    assert srv_s.aggregator.peak_buffered_updates <= 2
    # legacy buffers the whole cohort
    assert srv_l.aggregator.peak_buffered_updates >= 4
    assert _leaves_equal(srv_s.aggregator.global_vars,
                         srv_l.aggregator.global_vars)
    _ = jax  # keep the import for device_get inside _leaves_equal


def test_stream_qsgd8_quantize_then_mask(eight_devices):
    """comm_compression=qsgd8 and SecAgg STACK: masked int8-grid deltas on
    the u16 ring wire (4-cohort), 2x under the dense f32 equivalent, and
    the run still learns."""
    from fedml_tpu.comm import codecs

    before = codecs.PAYLOAD_BYTES.value(codec="secagg_qsgd8")
    raw_before = codecs.PAYLOAD_RAW_BYTES.value(codec="secagg_qsgd8")
    cfg = _sa_config(run_id="sas2", frequency_of_the_test=1,
                     extra={"comm_compression": "qsgd8"})
    h, srv = _run_sa(cfg)
    assert srv.aggregator.ring.codec == "qsgd8"
    assert srv.aggregator.ring.bits == 11  # 8 value bits + 2 carry + 1 sign
    assert srv.aggregator.peak_buffered_updates <= 2
    assert h[-1]["test_acc"] > 0.4, h
    wire = codecs.PAYLOAD_BYTES.value(codec="secagg_qsgd8") - before
    raw = codecs.PAYLOAD_RAW_BYTES.value(codec="secagg_qsgd8") - raw_before
    assert wire > 0 and raw / wire >= 1.9, (raw, wire)


def test_stream_dropout_before_upload_bitwise(eight_devices):
    """Client 4 completes setup but never uploads: the streamed round
    reconstructs s_sk_4 from the reveals and cancels its orphaned pair
    masks from SEEDS at finalize (never re-buffering) — bitwise the legacy
    dropout round."""
    extra = {"straggler_timeout_s": 2.0, "straggler_quorum_frac": 0.5,
             "secagg_privacy_t": 2}
    h_s, srv_s = _run_sa(_sa_config(run_id="sas3", comm_round=1, extra=extra),
                         drop_ranks=frozenset({4}))
    h_l, srv_l = _run_sa(
        _sa_config(run_id="sas3l", comm_round=1,
                   extra=dict(extra, secagg_stream=False)),
        drop_ranks=frozenset({4}))
    assert len(h_s) == len(h_l) == 1
    assert 4 in srv_s.aggregator.compromised
    assert srv_s.aggregator.peak_buffered_updates <= 2
    assert _leaves_equal(srv_s.aggregator.global_vars,
                         srv_l.aggregator.global_vars)


def test_stream_dropout_after_upload_and_during_finalize(eight_devices):
    """Client 4 uploads its masked model, then vanishes BEFORE the reveal
    phase (drops during finalize): the reveal-phase straggler timeout
    proceeds with the T+1 surviving reveals, client 4's self-mask is
    reconstructed from its PEERS' b-shares, and its upload stays in the
    aggregate — bitwise the full-participation run."""
    import fedml_tpu
    from fedml_tpu.comm.inproc import InProcRouter
    from fedml_tpu.cross_silo.secagg_shamir import build_sa_client, build_sa_server
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    # the upload phase must NOT time out here (all four upload): 2 s was not
    # enough for four training threads on a box whose other five test workers
    # are compiling; the reveal phase waits this long for client 4
    extra = {"straggler_timeout_s": 6.0, "straggler_quorum_frac": 0.5,
             "secagg_privacy_t": 2}
    cfg = _sa_config(run_id="sas4", comm_round=1, extra=extra)
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    InProcRouter.reset(str(cfg.run_id))
    clients = [build_sa_client(cfg, ds, model, rank=r, backend="INPROC")
               for r in range(1, 5)]
    # rank 4 trains + uploads, then never answers the ACTIVE_SET request
    clients[3].handle_message_active_set = lambda msg: None
    for c in clients:
        c.run_in_thread()
    server = build_sa_server(cfg, ds, model, backend="INPROC")
    try:
        history = server.run_until_done(timeout=120.0)
    finally:
        for c in clients:
            c.finish()
    assert len(history) == 1
    assert server.aggregator.peak_buffered_updates <= 2
    # all four uploads are in the sum: equals the no-dropout legacy run
    _, srv_full = _run_sa(_sa_config(run_id="sas4l", comm_round=1,
                                     extra=dict(extra, secagg_stream=False)))
    assert _leaves_equal(server.aggregator.global_vars,
                         srv_full.aggregator.global_vars)


# -- 4) central DP at finalize ------------------------------------------------

def test_central_dp_exactly_once_at_finalize(eight_devices):
    """enable_dp + cdp composes with secagg_stream (LDP stays refused): the
    noise lands once, deterministically from the round key, via the Pallas
    noise path — pinned against the manual clip+noise of the no-DP run's
    aggregate."""
    import jax
    import jax.flatten_util
    import jax.numpy as jnp

    from fedml_tpu.core import rng as rnglib
    from fedml_tpu.ops.pallas import noise as nz
    from fedml_tpu.trust.dp.dp import clip_by_norm, gaussian_sigma

    dp_kw = dict(enable_dp=True, dp_solution_type="cdp",
                 mechanism_type="gaussian", epsilon=50.0, delta=1e-5,
                 sensitivity=0.01, clipping_norm=1.0)
    h_dp, srv_dp = _run_sa(_sa_config(run_id="sas5", comm_round=1, **dp_kw))
    h_dp2, srv_dp2 = _run_sa(_sa_config(run_id="sas5b", comm_round=1, **dp_kw))
    h_plain, srv_plain = _run_sa(_sa_config(run_id="sas5p", comm_round=1))
    # deterministic: two DP runs agree bitwise; and DP actually changed it
    assert _leaves_equal(srv_dp.aggregator.global_vars,
                         srv_dp2.aggregator.global_vars)
    assert not _leaves_equal(srv_dp.aggregator.global_vars,
                             srv_plain.aggregator.global_vars)
    # manual expectation from the no-DP aggregate (noise applied ONCE);
    # the initial global is deterministic from random_seed — no run needed
    import fedml_tpu
    from fedml_tpu.cross_silo.secagg_shamir import build_sa_server
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    icfg = _sa_config(run_id="sas5i", comm_round=1)
    fedml_tpu.init(icfg)
    ids = loader.load(icfg)
    init_srv = build_sa_server(icfg, ids, model_hub.create(icfg, ids.class_num),
                               backend="INPROC")
    init_flat, _ = jax.flatten_util.ravel_pytree(init_srv.aggregator.global_vars)
    init_srv.finish()
    agg_flat, _ = jax.flatten_util.ravel_pytree(srv_plain.aggregator.global_vars)
    delta = clip_by_norm(jnp.asarray(agg_flat) - jnp.asarray(init_flat), 1.0)
    key = jax.random.fold_in(rnglib.round_key(rnglib.root_key(0), 0), 0xCD9)
    sigma = gaussian_sigma(50.0, 1e-5, 0.01)
    expect = nz.apply_gaussian_noise(jnp.asarray(init_flat) + delta, key, sigma,
                                     interpret=True)
    got, _ = jax.flatten_util.ravel_pytree(srv_dp.aggregator.global_vars)
    assert np.array_equal(np.asarray(got), np.asarray(expect, np.float32))


def test_ldp_with_secagg_still_refused():
    from fedml_tpu.cross_silo.secagg_shamir import shamir_secagg_params

    cfg = _sa_config(run_id="sas6", enable_dp=True, dp_solution_type="ldp")
    with pytest.raises(NotImplementedError, match="enable_dp"):
        shamir_secagg_params(cfg)
    # and cdp WITHOUT the streaming fold keeps the historical refusal
    cfg2 = _sa_config(run_id="sas6b", enable_dp=True, dp_solution_type="cdp",
                      extra={"secagg_stream": False})
    with pytest.raises(NotImplementedError, match="enable_dp"):
        shamir_secagg_params(cfg2)


# -- 5) trust gate: stream where sound, exact everywhere else -----------------

def _plain_aggregator(run_id, trust=True, **kw):
    import fedml_tpu
    from fedml_tpu.cross_silo.server import FedMLAggregator
    from fedml_tpu.data import loader
    from fedml_tpu.data.dataset import pad_eval_set
    from fedml_tpu.models import model_hub
    from fedml_tpu.trust.pipeline import build_trust_pipeline

    base = dict(client_num_in_total=2, client_num_per_round=2, comm_round=1,
                epochs=1, batch_size=16, synthetic_train_size=128,
                synthetic_test_size=64, training_type="cross_silo",
                frequency_of_the_test=0, run_id=run_id)
    base.update(kw)
    cfg = tiny_config(**base)
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    tp = build_trust_pipeline(cfg) if trust else None
    return FedMLAggregator(cfg, model, ds.train_x[:16],
                           pad_eval_set(ds.test_x, ds.test_y, 32), trust=tp), ds


def _feed_two(agg, base):
    from fedml_tpu.comm.message import Message
    from fedml_tpu.cross_silo import message_define as md

    import jax

    for cid in (1, 2):
        rs = np.random.RandomState(cid)
        params = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32)
            + rs.randn(*np.shape(x)).astype(np.float32), base)
        if agg.stream_mode:
            m = Message(md.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, cid, 0)
            m.add_params(md.MSG_ARG_KEY_MODEL_PARAMS, params)
            assert agg.ingest_streaming(cid, Message.decode(m.encode()), 64.0,
                                        is_delta=False)
        else:
            agg.add_local_trained_result(cid, params, 64.0)


def test_cdp_trust_streams_bitwise_sync_and_async_flags(eight_devices):
    """The gate relaxation: a CDP-only trust pipeline no longer forces
    exact mode — under either the sync streaming flag or the async flag the
    fold engages, and the finalized (clipped+noised) global is BITWISE the
    exact buffer-all CDP result."""
    import jax

    dp = dict(enable_dp=True, dp_solution_type="cdp", mechanism_type="gaussian",
              epsilon=100.0, delta=1e-5, sensitivity=0.01, clipping_norm=1.0)
    stream, _ = _plain_aggregator("tg1", extra={"streaming_aggregation": True}, **dp)
    async_agg, _ = _plain_aggregator("tg2", extra={"async_aggregation": True}, **dp)
    exact, _ = _plain_aggregator("tg3", **dp)
    assert stream.stream_mode and async_agg.stream_mode
    assert not exact.stream_mode
    base = jax.device_get(exact.global_vars)
    _feed_two(stream, base)
    _feed_two(exact, base)
    assert stream._stream_folded == 2 and exact._stream_folded == 0
    assert _leaves_equal(stream.aggregate(0), exact.aggregate(0))


def test_defense_ldp_fhe_salsa_still_exact(eight_devices):
    """Regression pins (ISSUE 15 satellite): every configuration that needs
    the stacked per-client matrix still takes the buffer-all path exactly
    as before the PR — the fold NEVER engages."""
    import fedml_tpu

    # defense-configured: stacked matrix needed -> exact
    dfn, _ = _plain_aggregator(
        "tg4", enable_defense=True, defense_type="norm_diff_clipping",
        extra={"streaming_aggregation": True})
    assert not dfn.stream_mode
    # LDP: per-client noise -> exact
    ldp, _ = _plain_aggregator(
        "tg5", enable_dp=True, dp_solution_type="ldp",
        extra={"streaming_aggregation": True})
    assert not ldp.stream_mode
    # FHE aggregator: ciphertext stacks -> pinned exact whatever the flags
    from fedml_tpu.cross_silo.fhe import FHEAggregator
    from fedml_tpu.data import loader as dloader
    from fedml_tpu.data.dataset import pad_eval_set
    from fedml_tpu.models import model_hub

    fcfg = tiny_config(
        client_num_in_total=2, client_num_per_round=2, comm_round=1,
        training_type="cross_silo", enable_fhe=True, run_id="tg6",
        extra={"streaming_aggregation": True, "comm_compression": "qsgd8",
               "fhe_ring_dim": 256})
    fedml_tpu.init(fcfg)
    fds = dloader.load(fcfg)
    fmodel = model_hub.create(fcfg, fds.class_num)
    fhe = FHEAggregator(fcfg, fmodel, fds.train_x[:16],
                        pad_eval_set(fds.test_x, fds.test_y, 32))
    assert not fhe.stream_mode
    assert fhe.fold(1, object(), 1.0, False) is False
    # SA/LSA keep the base f32 fold pinned off (their own field fold is
    # separate machinery behind secagg_stream)
    from fedml_tpu.cross_silo.secagg_shamir import SAAggregator

    scfg = _sa_config(run_id="tg7", extra={"comm_compression": "qsgd8"})
    fedml_tpu.init(scfg)
    sds = dloader.load(scfg)
    smodel = model_hub.create(scfg, sds.class_num)
    sa = SAAggregator(scfg, smodel, sds.train_x[:16],
                      pad_eval_set(sds.test_x, sds.test_y, 32))
    assert not sa.stream_mode and sa.field_stream


def test_lsa_stream_bitwise_vs_legacy(eight_devices):
    """LightSecAgg rides the same field fold: the O(cohort * d) masked-model
    buffer streams (peak <= 2), the aggregate-mask decode is untouched, and
    the final global is bitwise the buffer-all run's."""
    import fedml_tpu
    from fedml_tpu.cross_silo.lightsecagg import run_lightsecagg_process_group
    from fedml_tpu.data import loader
    from fedml_tpu.models import model_hub

    def lsa_cfg(run_id, stream):
        return tiny_config(
            client_num_in_total=4, client_num_per_round=4, comm_round=1,
            epochs=1, batch_size=16, synthetic_train_size=256,
            synthetic_test_size=64, training_type="cross_silo",
            enable_secagg=True, frequency_of_the_test=0, run_id=run_id,
            extra={"secagg_stream": stream})

    cfg = lsa_cfg("lsa_s", True)
    fedml_tpu.init(cfg)
    ds = loader.load(cfg)
    model = model_hub.create(cfg, ds.class_num)
    h_s, srv_s = run_lightsecagg_process_group(cfg, ds, model, timeout=120.0)
    cfg_l = lsa_cfg("lsa_l", False)
    fedml_tpu.init(cfg_l)
    h_l, srv_l = run_lightsecagg_process_group(cfg_l, ds, model, timeout=120.0)
    assert len(h_s) == len(h_l) == 1
    assert srv_s.aggregator.peak_buffered_updates <= 2
    assert srv_l.aggregator.peak_buffered_updates >= 4
    assert _leaves_equal(srv_s.aggregator.global_vars,
                         srv_l.aggregator.global_vars)


# -- 6) soak + satellites -----------------------------------------------------

def test_secagg_soak_smoke():
    from fedml_tpu.cross_silo.secagg_soak import run_secagg_stream_soak

    res = run_secagg_stream_soak(cohort=128, dim=1024, rounds=1,
                                 drop_before_frac=0.02, drop_after_frac=0.02)
    assert res["bitwise_identity"] and res["peak_buffered"] <= 2
    assert res["dropped_before"] >= 2 and res["dropped_after"] >= 2
    assert res["bytes_per_round"] < res["bytes_per_round_dense_mask"]
    assert res["bytes_per_round_dense_mask"] < res["bytes_per_round_legacy_int64"]
    dense = run_secagg_stream_soak(cohort=64, dim=512, rounds=1, codec="dense")
    assert dense["bitwise_identity"] and dense["peak_buffered"] <= 2


def test_secagg_modules_hold_no_legacy_extra_idioms():
    """ISSUE-15 lint satellite, regression-pinned: the secagg modules carry
    ZERO statement-position ``extra`` setdefault/subscript/``in`` sites (the
    reported-only class lint --fix never auto-rewrites) and zero rewritable
    legacy reads."""
    import os

    from fedml_tpu.analysis.fix import fix_source

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for mod in ("lightsecagg.py", "secagg_shamir.py", "secagg_soak.py"):
        path = os.path.join(pkg, "fedml_tpu", "cross_silo", mod)
        with open(path) as f:
            src = f.read()
        _, rewrites, skipped = fix_source(src, f"cross_silo/{mod}")
        assert rewrites == 0, (mod, rewrites)
        assert skipped == [], (mod, skipped)
