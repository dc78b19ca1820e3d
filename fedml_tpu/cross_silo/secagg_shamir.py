"""Shamir pairwise-mask SecAgg — the reference's SECOND secure-agg protocol.

Wire parity with ``cross_silo/secagg/sa_fedml_server_manager.py:14`` /
``sa_fedml_client_manager.py:20`` / ``sa_fedml_aggregator.py:18`` (the
Bonawitz-style protocol; LightSecAgg is the other variant, `lightsecagg.py`).
Message flow (reference ``sa_message_define.py`` + manager handlers):

    PK           (c_pk, s_pk)                       client -> server    (setup)
    PK TABLE     all public keys                    server -> clients   (setup)
    SHARES       Shamir shares of (b_u, s_sk_u)     client -> server -> peers
    --- each client holds one share of every peer's secrets ---
    INIT/SYNC    global model                       server -> clients
    masked model quantize(x_u) + PRG(b_u)
                 + sum_{v<u} PRG(s_uv) - sum_{v>u} PRG(s_uv)   client -> server
    ACTIVE SET   first-round survivors              server -> survivors
    REVEAL       b-share of survivors,
                 s_sk-share of dropped              survivor -> server
    --- >= T+1 reveals: server reconstructs, unmasks the SUM, averages ---

Reconstruction (reference ``sa_fedml_aggregator.py:92-135``): for every
SURVIVOR u the server Shamir-decodes the self-mask seed b_u and subtracts
PRG(b_u); for every DROPPED u it decodes s_sk_u, re-derives the pairwise
agreements s_uv with each survivor's s_pk, and cancels the orphaned halves of
the pair masks.  A client's b-share and s_sk-share are never both revealed,
so no individual update can be unmasked as long as < T+1 parties collude.

Deliberate divergences from the reference (each strengthens the protocol —
the masking equation and message flow are unchanged):

- **Real key exchange.** The reference's ``my_pk_gen(sk, p, g=0)`` RETURNS
  THE SECRET KEY as the "public key" (``core/mpc/secagg.py:329-342``: g==0 ->
  pk = sk, agreement = sk_u * pk_v), so every mask seed is derivable from
  wire traffic.  Here pk = g^sk mod p (g=5) and agreement = pk_v^sk_u mod p —
  a true DH shape.  (M31 is a toy group — smooth order, Pohlig-Hellman
  breakable; a production deployment swaps in X25519.  The reference has no
  group at all.)
- **Encrypted share transit.** The reference server stores every client's
  full share vectors (``sa_fedml_server_manager.py:158-168``:
  ``b_u_SS_list``/``s_sk_SS_list``), letting it reconstruct any secret alone.
  Here a share for peer v travels under a pad derived from the c-key
  agreement between u and v; the server relays ciphertext it cannot read.
- **Per-round mask seeds.** The reference reseeds ``np.random.seed(b_u)``
  with the SAME b_u every round (``sa_fedml_client_manager.py:227``) — masks
  repeat, so two rounds' uploads differ by exactly the model delta.  Here
  every round derives fresh seeds via SHA-256(seed, round).
- Secrets come from OS entropy, not ``np.random.seed(rank)``
  (``sa_fedml_client_manager.py:273``, which makes every "secret" public).
"""

from __future__ import annotations

import hashlib
import logging
import math
import os
import threading
from typing import Optional

import jax.flatten_util
import jax.numpy as jnp
import numpy as np

from ..comm import codecs
from ..comm.message import Message
from ..core import rng
from ..core.flags import cfg_extra
from ..trust.secagg import stream as secagg_stream
from ..trust.secagg.field import DEFAULT_PRIME, dequantize_from_field, quantize_to_field
from ..trust.secagg.shamir import (
    masked_input,
    pairwise_mask,
    shamir_reconstruct,
    shamir_share,
    unmask_sum,
)
from . import message_define as md
from .client import ClientMasterManager, FedMLTrainer
from .server import FedMLAggregator, FedMLServerManager

log = logging.getLogger("fedml_tpu.cross_silo.secagg_shamir")

# protocol constants — continue the flat cross-silo namespace (0-8 core,
# 10-13 LightSecAgg)
MSG_TYPE_C2S_PUBLIC_KEY = 14      # ref MSG_TYPE_C2S_SEND_PK_TO_SERVER = 3
MSG_TYPE_S2C_PUBLIC_KEYS = 15     # ref MSG_TYPE_S2C_OTHER_PK_TO_CLIENT = 4
MSG_TYPE_C2S_SECRET_SHARES = 16   # ref MSG_TYPE_C2S_SEND_SS_TO_SERVER = 5
MSG_TYPE_S2C_PEER_SHARES = 17     # ref MSG_TYPE_S2C_OTHER_SS_TO_CLIENT = 6
MSG_TYPE_S2C_ACTIVE_SET = 18      # ref MSG_TYPE_S2C_ACTIVE_CLIENT_LIST = 10
MSG_TYPE_C2S_SHARE_REVEAL = 19    # ref MSG_TYPE_C2S_SEND_SS_OTHERS_TO_SERVER = 11

MSG_ARG_KEY_C_PK = "c_pk"
MSG_ARG_KEY_S_PK = "s_pk"
MSG_ARG_KEY_PK_TABLE = "pk_table"
MSG_ARG_KEY_B_SHARES = "b_shares_enc"
MSG_ARG_KEY_SK_SHARES = "sk_shares_enc"
MSG_ARG_KEY_SHARE_SOURCE = "share_source"
MSG_ARG_KEY_ACTIVE_SET = "active_set"
MSG_ARG_KEY_B_REVEALS = "b_reveals"
MSG_ARG_KEY_SK_REVEALS = "sk_reveals"
#: control-plane descriptor of a streaming masked upload (codec, ring_bits,
#: frac_bits, length, delta) — present only when extra.secagg_stream is set,
#: so the legacy wire stays byte-identical
MSG_ARG_KEY_SECAGG_META = "secagg_meta"

P = DEFAULT_PRIME
DH_G = 5


def dh_keypair() -> tuple[int, int]:
    sk = int.from_bytes(os.urandom(16), "little") % (P - 3) + 2
    return sk, pow(DH_G, sk, P)


def dh_agree(sk: int, peer_pk: int) -> int:
    return pow(int(peer_pk), int(sk), P)


def derive_round_seed(seed: int, round_idx: int) -> int:
    """Fresh 31-bit PRG seed per (secret, round) — masks never repeat across
    rounds (unlike reference ``sa_fedml_client_manager.py:227``)."""
    h = hashlib.sha256(f"sa:{int(seed)}:{int(round_idx)}".encode()).digest()
    return int.from_bytes(h[:4], "little") % (2**31)


def _share_pad(c_key: int, src: int, dst: int) -> tuple[int, int]:
    """Keystream hiding a (b, s_sk) share pair in server transit, derived
    from the c-key agreement the server does not know.  Bound to the
    DIRECTION and share kind: the u<->v agreement is symmetric, so a pad
    derived from the key alone would repeat for u->v and v->u, and a later
    plaintext b-share reveal would hand the server a known-plaintext recovery
    of the sibling s_sk pad.  Hashing (key, src, dst, kind) makes every pad
    element independent."""
    def h(kind: str) -> int:
        d = hashlib.sha256(f"pad:{int(c_key)}:{int(src)}:{int(dst)}:{kind}".encode()).digest()
        return int.from_bytes(d[:8], "little") % P

    return h("b"), h("sk")


def shamir_secagg_params(cfg):
    """(T, q_bits): T = privacy threshold, reconstruction needs T+1 shares
    (reference ``sa_fedml_aggregator.py:53``: T = floor(N/2))."""
    n = cfg.client_num_in_total
    t = int(cfg_extra(cfg, "secagg_privacy_t", max(1, n // 2)))
    q_bits = int(cfg_extra(cfg, "secagg_q_bits"))
    if not (0 < t < n):
        raise ValueError(f"Shamir SecAgg needs 0 < T({t}) < N({n})")
    # central DP composes with the STREAMING fold (ISSUE 15): the noise is
    # added exactly once, to the unmasked aggregate at finalize — it never
    # needs the individual updates SecAgg hides.  LDP (and everything else
    # below) still does, and stays refused.
    streaming_cdp_ok = bool(cfg_extra(cfg, "secagg_stream")) and (
        getattr(cfg, "dp_solution_type", "ldp").lower() == "cdp")
    incompatible = [
        f for f in ("enable_attack", "enable_defense", "enable_dp", "enable_contribution", "enable_fhe")
        if getattr(cfg, f, False) and not (f == "enable_dp" and streaming_cdp_ok)
    ]
    if incompatible:
        raise NotImplementedError(
            f"trust features {incompatible} operate on individual client "
            "updates, which SecAgg hides from the server by design; disable "
            "them or disable enable_secagg (central DP composes when "
            "secagg_stream is set: noise lands once on the unmasked "
            "aggregate at finalize)"
        )
    if getattr(cfg, "federated_optimizer", "FedAvg") not in ("FedAvg", "fedavg", "FedAvg_seq"):
        raise NotImplementedError(
            "SecAgg reconstruction yields only the uniform mean of the "
            "survivors' updates (reference sa_fedml_aggregator.py:182); "
            f"{cfg.federated_optimizer!r} needs per-client updates"
        )
    from ..fl.algorithm import config_supports_associative_fold

    if not config_supports_associative_fold(cfg):
        # the masked field total IS an associative fold — an algorithm whose
        # aggregate is order- or set-sensitive cannot ride it (same protocol
        # gate as the f32 streaming accumulator, fl/algorithm.py)
        raise NotImplementedError(
            "SecAgg's masked sum is a weight-associative fold; the "
            "configured algorithm overrides aggregate() and does not "
            "declare supports_associative_fold"
        )
    return t, q_bits


class SAAggregator(FedMLAggregator):
    """Server-side state: masked field vectors + revealed shares."""

    def __init__(self, cfg, model, sample_x, test_arrays, trust=None):
        super().__init__(cfg, model, sample_x, test_arrays, trust=trust)
        # masked field vectors are not foldable f32 trees: the base f32
        # streaming path must NEVER engage here, whatever the comm flags say
        # (regression-tested — the LoRA opt-in must not bypass this).  The
        # FIELD-domain streaming fold below (extra.secagg_stream) is this
        # protocol's own fast path.
        self.stream_mode = False
        self._shard_fold = False
        self.t, self.q_bits = shamir_secagg_params(cfg)
        flat, self._unravel = jax.flatten_util.ravel_pytree(self.global_vars)
        self.model_dim = int(flat.size)
        self.n = cfg.client_num_in_total
        # streaming masked folds (ISSUE 15): each arriving masked upload
        # folds into a running field total — peak buffered <= 2 at any
        # cohort size — and the masks come out once, at finalize.  Flag
        # unset -> the historical buffer-all path, bit-identical.
        self.field_stream = bool(cfg_extra(cfg, "secagg_stream"))
        self.ring = secagg_stream.ring_for(
            codecs.codec_from_config(cfg), self.n, q_bits=self.q_bits,
            q8_frac_bits=int(cfg_extra(cfg, "secagg_q8_frac_bits")))
        self._msum: Optional[secagg_stream.StreamingMaskedSum] = None
        self._stream_is_delta = False
        # central DP at finalize (streaming only; shamir_secagg_params
        # refuses every other trust composition)
        self._dp = None
        if getattr(cfg, "enable_dp", False):
            from ..trust.dp.dp import FedMLDifferentialPrivacy

            self._dp = FedMLDifferentialPrivacy(cfg)
        self.s_pk_table: dict[int, int] = {}
        # reveals[v] = (b_reveals {u: y}, sk_reveals {u: y}) from survivor v
        self.reveals: dict[int, tuple[dict, dict]] = {}
        # clients whose s_sk was reconstructed after a dropout: their pairwise
        # seeds are known to the server, so a later rejoin would let it also
        # learn b_u (revealed for survivors) and fully unmask that client's
        # upload.  Secrets are exchanged once per run, so the only sound move
        # is PERMANENT exclusion (the reference instead re-runs its offline
        # phase every round).
        self.compromised: set[int] = set()

    def add_local_trained_result(self, client_idx: int, masked_vec, sample_num: float) -> None:
        if client_idx in self.compromised:
            log.warning(
                "client %d rejoined after its s_sk was reconstructed; refusing "
                "its upload (accepting would reveal BOTH of its secrets)",
                client_idx,
            )
            return
        vec = np.asarray(masked_vec, dtype=np.int64)
        if vec.shape != (self.model_dim,):
            raise ValueError(f"masked vector shape {vec.shape} != ({self.model_dim},)")
        super().add_local_trained_result(client_idx, vec, sample_num)

    def add_masked_upload(self, client_idx: int, packed, sample_num: float,
                          meta: dict) -> None:
        """Streaming path (extra.secagg_stream): unpack the wire-width
        masked vector and fold it into the running field total IMMEDIATELY
        — nothing cohort-sized is ever buffered.  The packed form is freed
        as soon as the fold returns, so the peak is the total plus the one
        in-flight upload."""
        if client_idx in self.compromised:
            log.warning(
                "client %d rejoined after its s_sk was reconstructed; refusing "
                "its upload (accepting would reveal BOTH of its secrets)",
                client_idx,
            )
            return
        if not self.ring.matches(meta):
            log.warning("client %d masked upload ring %s != server %s; "
                        "rejecting", client_idx, meta, self.ring.meta(0))
            return
        vec = secagg_stream.unpack_ring(
            packed, self.ring.bits, int(meta.get("length", self.model_dim)))
        if vec.shape != (self.model_dim,):
            raise ValueError(f"masked vector shape {vec.shape} != ({self.model_dim},)")
        if self._msum is None:
            self._msum = secagg_stream.StreamingMaskedSum(self.model_dim, self.ring)
        self._stream_is_delta = bool(meta.get("delta"))
        self._msum.fold(vec)
        self.sample_num_dict[client_idx] = sample_num
        self.flag_client_model_uploaded[client_idx] = True
        self.peak_buffered_updates = max(self.peak_buffered_updates,
                                         self._msum.peak_buffered)

    def survivor_ids(self) -> list[int]:
        """Clients whose (masked) upload is in this round's sum — the one
        ledger both the buffer-all and streaming paths maintain."""
        return sorted(self.flag_client_model_uploaded)

    def add_reveal(self, sender: int, b_reveals: dict, sk_reveals: dict) -> None:
        self.reveals[int(sender)] = (
            {int(u): int(y) for u, y in b_reveals.items()},
            {int(u): int(y) for u, y in sk_reveals.items()},
        )

    def reveal_count(self) -> int:
        return len(self.reveals)

    def aggregate(self, round_idx: int):
        """Reference ``aggregate_model_reconstruction`` + ``aggregate_mask_
        reconstruction`` (``sa_fedml_aggregator.py:92-188``): decode survivors'
        b_u -> subtract self-masks; decode dropped s_sk_u -> cancel orphaned
        pairwise masks; dequantize; uniform average.

        With ``extra.secagg_stream`` the sum already exists — every upload
        folded into the field total as it arrived — so finalize is just the
        seed reconstruction (tiny scalars from the reveals), the unmask over
        ONE vector, and an optional single central-DP noise draw.  The
        mod-field math is exact, so the streamed result is BITWISE the
        buffer-all result."""
        active = self.survivor_ids()
        dropped = [u for u in range(1, self.n + 1) if u not in active]

        self_seeds = {}
        for u in active:
            shares = [(v, self.reveals[v][0][u]) for v in self.reveals if u in self.reveals[v][0]]
            if len(shares) < self.t + 1:
                raise RuntimeError(f"not enough b-shares for survivor {u}: {len(shares)}")
            b_u = shamir_reconstruct(shares[: self.t + 1])
            self_seeds[u] = derive_round_seed(b_u, round_idx)

        dropped_pair_seeds = {}
        for u in dropped:
            shares = [(v, self.reveals[v][1][u]) for v in self.reveals if u in self.reveals[v][1]]
            if len(shares) < self.t + 1:
                raise RuntimeError(f"not enough s_sk-shares for dropped {u}: {len(shares)}")
            s_sk_u = shamir_reconstruct(shares[: self.t + 1])
            self.compromised.add(u)  # its pairwise seeds are now server-known
            for v in active:
                s_uv = dh_agree(s_sk_u, self.s_pk_table[v])
                dropped_pair_seeds[(u, v)] = derive_round_seed(s_uv, round_idx)

        if self._msum is not None:
            total = self._msum.finalize(self_seeds, dropped_pair_seeds)
            avg = dequantize_from_field(
                total, len(active), p=self.ring.modulus, bits=self.ring.frac_bits)
            avg = avg / max(len(active), 1)
            if self._stream_is_delta:
                # qsgd8 composition ships quantized DELTAS vs the round's
                # broadcast global: the unmasked mean delta lands on it
                old_flat, _ = jax.flatten_util.ravel_pytree(self.global_vars)
                avg = np.asarray(old_flat, np.float64) + avg
        else:
            masked = {u: self.model_dict[u] for u in active}
            total = unmask_sum(masked, self_seeds, dropped_pair_seeds)
            avg = dequantize_from_field(total, len(active), bits=self.q_bits)
            avg = avg / max(len(active), 1)
        avg = self._apply_central_dp(avg, round_idx)
        self.global_vars = self._unravel(jnp.asarray(avg, jnp.float32))
        self.model_dict.clear()
        self.sample_num_dict.clear()
        self.flag_client_model_uploaded.clear()
        self.reveals.clear()
        self._msum = None
        self._stream_is_delta = False
        return self.global_vars

    def _apply_central_dp(self, avg: np.ndarray, round_idx: int) -> np.ndarray:
        """Central DP, EXACTLY ONCE, at finalize (ISSUE 15): clip the
        aggregate's round delta and add calibrated noise on the Pallas RNG
        path (``ops/pallas/noise.py`` — noise drawn from the round key, the
        scale-and-add fused).  Engaged only when ``shamir_secagg_params``
        admitted the enable_dp + secagg_stream + CDP composition."""
        if self._dp is None or not self._dp.is_cdp_enabled():
            return avg
        from ..ops.pallas import noise as pallas_noise
        from ..trust.dp.dp import gaussian_sigma

        old_flat, _ = jax.flatten_util.ravel_pytree(self.global_vars)
        delta = jnp.asarray(avg, jnp.float32) - jnp.asarray(old_flat, jnp.float32)
        delta = self._dp.global_clip(delta)
        flat = jnp.asarray(old_flat, jnp.float32) + delta
        key = jax.random.fold_in(rng.round_key(self.root_key, round_idx), 0xCD9)
        if self._dp.mechanism == "gaussian":
            sigma = gaussian_sigma(self._dp.epsilon, self._dp.delta,
                                   self._dp.sensitivity)
            noised = pallas_noise.apply_gaussian_noise(flat, key, sigma)
        else:
            noised = self._dp.add_global_noise(flat, key)
        return np.asarray(noised, np.float64)


class SAServerManager(FedMLServerManager):
    """Reference ``FedMLServerManager`` (secagg): PK collection/broadcast,
    encrypted share relay, active-set announcement, reveal collection."""

    def __init__(self, cfg, aggregator: SAAggregator, backend: Optional[str] = None, logger=None):
        super().__init__(cfg, aggregator, backend=backend, logger=logger)
        if self.per_round != len(self.client_ids):
            raise ValueError(
                "Shamir SecAgg requires full participation per round "
                f"(client_num_per_round={self.per_round} != N={len(self.client_ids)}); "
                "the pairwise-mask topology is over all N clients"
            )
        self.n = cfg.client_num_in_total
        self.pk_table: dict[int, tuple[int, int]] = {}
        # share_box[dest] = {src: (b_share_enc, sk_share_enc)}
        self.share_box: dict[int, dict[int, tuple[int, int]]] = {v: {} for v in self.client_ids}
        self.active_first: list[int] = []
        self._phase = "model"  # model -> reveal

    def register_message_receive_handlers(self) -> None:
        super().register_message_receive_handlers()
        self.register_message_receive_handler(MSG_TYPE_C2S_PUBLIC_KEY, self.handle_message_public_key)
        self.register_message_receive_handler(MSG_TYPE_C2S_SECRET_SHARES, self.handle_message_secret_shares)
        self.register_message_receive_handler(MSG_TYPE_C2S_SHARE_REVEAL, self.handle_message_reveal)

    # -- setup: PK round ------------------------------------------------------
    def handle_message_public_key(self, msg: Message) -> None:
        """Collect every client's (c_pk, s_pk); broadcast the full table once
        complete (reference ``_handle_message_receive_public_key`` :146)."""
        with self._agg_lock:
            self.pk_table[msg.get_sender_id()] = (
                int(msg.get(MSG_ARG_KEY_C_PK)), int(msg.get(MSG_ARG_KEY_S_PK))
            )
            self.aggregator.s_pk_table = {u: pk[1] for u, pk in self.pk_table.items()}
            complete = len(self.pk_table) == self.n
        if complete:
            table = {str(u): [int(c), int(s)] for u, (c, s) in self.pk_table.items()}
            for cid in self.client_ids:
                out = Message(MSG_TYPE_S2C_PUBLIC_KEYS, 0, cid)
                out.add_params(MSG_ARG_KEY_PK_TABLE, table)
                self.send_message(out)

    # -- setup: share relay ---------------------------------------------------
    def handle_message_secret_shares(self, msg: Message) -> None:
        """Store-and-forward: client u's encrypted share for peer v goes to v
        only — the server keeps ciphertext it cannot open (unlike reference
        ``sa_fedml_server_manager.py:158``, which stores plaintext shares)."""
        src = msg.get_sender_id()
        b_enc = np.asarray(msg.get(MSG_ARG_KEY_B_SHARES), dtype=np.int64)
        sk_enc = np.asarray(msg.get(MSG_ARG_KEY_SK_SHARES), dtype=np.int64)
        with self._agg_lock:
            for v in self.client_ids:
                self.share_box[v][src] = (int(b_enc[v - 1]), int(sk_enc[v - 1]))
            ready = all(len(self.share_box[v]) == self.n for v in self.client_ids)
        if ready:
            for v in self.client_ids:
                out = Message(MSG_TYPE_S2C_PEER_SHARES, 0, v)
                out.add_params(MSG_ARG_KEY_B_SHARES,
                               {str(u): b for u, (b, _) in self.share_box[v].items()})
                out.add_params(MSG_ARG_KEY_SK_SHARES,
                               {str(u): s for u, (_, s) in self.share_box[v].items()})
                self.send_message(out)

    # -- round: masked models -------------------------------------------------
    def handle_message_receive_model(self, msg: Message) -> None:
        with self._agg_lock:
            if msg.get(md.MSG_ARG_KEY_ROUND_INDEX) != self.round_idx or self._phase != "model":
                return
            meta = msg.get_control(MSG_ARG_KEY_SECAGG_META)
            if meta is not None:
                # streaming masked upload (extra.secagg_stream): folds into
                # the field total right here — never buffered
                self.aggregator.add_masked_upload(
                    msg.get_sender_id(),
                    msg.get(md.MSG_ARG_KEY_MODEL_PARAMS),
                    float(msg.get(md.MSG_ARG_KEY_NUM_SAMPLES)),
                    meta,
                )
            else:
                self.aggregator.add_local_trained_result(
                    msg.get_sender_id(),
                    msg.get(md.MSG_ARG_KEY_MODEL_PARAMS),
                    float(msg.get(md.MSG_ARG_KEY_NUM_SAMPLES)),
                )
            # permanently-excluded (compromised) clients never count toward
            # the expectation — their uploads are refused by the aggregator
            expected = len([c for c in self.selected if c not in self.aggregator.compromised])
            if self.aggregator.check_whether_all_receive(expected):
                self._request_reveals()

    def _request_reveals(self) -> None:
        """Freeze the survivor set, announce it, collect reveals (reference
        ``_send_message_to_active_client`` :313).  Caller holds _agg_lock."""
        self._runtime.cancel(self, "straggler")
        self._phase = "reveal"
        self.active_first = self.aggregator.survivor_ids()
        for cid in self.active_first:
            out = Message(MSG_TYPE_S2C_ACTIVE_SET, 0, cid)
            out.add_params(MSG_ARG_KEY_ACTIVE_SET, [int(c) for c in self.active_first])
            out.add_params(md.MSG_ARG_KEY_ROUND_INDEX, self.round_idx)
            self.send_message(out)
        self._arm_straggler_timer()

    def handle_message_reveal(self, msg: Message) -> None:
        with self._agg_lock:
            if msg.get(md.MSG_ARG_KEY_ROUND_INDEX) != self.round_idx or self._phase != "reveal":
                return
            self.aggregator.add_reveal(
                msg.get_sender_id(),
                msg.get(MSG_ARG_KEY_B_REVEALS),
                msg.get(MSG_ARG_KEY_SK_REVEALS),
            )
            if self.aggregator.reveal_count() >= len(self.active_first):
                self._phase = "model"
                self._finish_round()

    def _on_straggler_timeout(self) -> None:
        """Model phase: advance with a quorum; reveal phase: reconstruct as
        soon as >= T+1 reveals arrived (the hard decode threshold)."""
        with self._agg_lock:
            if self._phase == "model":
                # quorum over clients that CAN still upload: permanently
                # excluded (compromised) clients never will
                eligible = [c for c in self.selected if c not in self.aggregator.compromised]
                if len(eligible) < self.aggregator.t + 1:
                    self.failed = (
                        f"only {len(eligible)} eligible clients remain but "
                        f"reconstruction needs T+1={self.aggregator.t + 1}; "
                        "the run cannot make progress (too many permanently "
                        "excluded clients)"
                    )
                    log.error(self.failed)
                    self.send_finish()
                    return
                need = max(
                    self.aggregator.t + 1,
                    int(math.ceil(self.quorum_frac * len(eligible))),
                )
                if self.aggregator.received_count() >= need:
                    log.warning(
                        "round %d: straggler timeout, proceeding with %d/%d masked models",
                        self.round_idx, self.aggregator.received_count(), len(self.selected),
                    )
                    self._request_reveals()
                    return
            else:
                if self.aggregator.reveal_count() >= self.aggregator.t + 1:
                    log.warning(
                        "round %d: reveal-phase timeout, reconstructing from %d/%d reveals",
                        self.round_idx, self.aggregator.reveal_count(), len(self.active_first),
                    )
                    self._phase = "model"
                    self._finish_round()
                    return
            self._arm_straggler_timer()


class SAClientManager(ClientMasterManager):
    """Reference ``FedMLClientManager`` (secagg): keygen + share-out once,
    then per round: train, mask, upload; reveal on request."""

    def __init__(self, cfg, trainer: FedMLTrainer, rank: int, backend: Optional[str] = None):
        super().__init__(cfg, trainer, rank=rank, backend=backend)
        self.t, self.q_bits = shamir_secagg_params(cfg)
        self.n = cfg.client_num_in_total
        # streaming masked uploads (ISSUE 15): quantize(-then-mask) into the
        # cohort-sized ring and ship the minimal wire dtype; flag unset ->
        # the historical int64 field vector, byte-identical
        self.stream = bool(cfg_extra(cfg, "secagg_stream"))
        self.ring = secagg_stream.ring_for(
            codecs.codec_from_config(cfg), self.n, q_bits=self.q_bits,
            q8_frac_bits=int(cfg_extra(cfg, "secagg_q8_frac_bits")))
        # secrets from OS entropy (reference seeds np.random with the RANK,
        # sa_fedml_client_manager.py:273 — making every secret public)
        self.c_sk, self.c_pk = dh_keypair()
        self.s_sk, self.s_pk = dh_keypair()
        self.b_u = int.from_bytes(os.urandom(8), "little") % (2**31)
        self.pk_table: dict[int, tuple[int, int]] = {}
        # held_shares[u] = (b_share_y, sk_share_y) with x = own rank
        self.held_shares: dict[int, tuple[int, int]] = {}
        self._setup_done = threading.Event()
        self._pending_msg: Optional[Message] = None
        self._lock = threading.Lock()
        self._shared_out = False

    def register_message_receive_handlers(self) -> None:
        super().register_message_receive_handlers()
        self.register_message_receive_handler(MSG_TYPE_S2C_PUBLIC_KEYS, self.handle_message_pk_table)
        self.register_message_receive_handler(MSG_TYPE_S2C_PEER_SHARES, self.handle_message_peer_shares)
        self.register_message_receive_handler(MSG_TYPE_S2C_ACTIVE_SET, self.handle_message_active_set)

    # -- setup ----------------------------------------------------------------
    def _train_and_send(self, msg: Message) -> None:
        """INIT/SYNC: run setup lazily on the first round, then train+mask."""
        with self._lock:
            self._pending_msg = msg
        if not self._setup_done.is_set():
            if not self.pk_table:
                out = Message(MSG_TYPE_C2S_PUBLIC_KEY, self.rank, 0)
                out.add_params(MSG_ARG_KEY_C_PK, int(self.c_pk))
                out.add_params(MSG_ARG_KEY_S_PK, int(self.s_pk))
                self.send_message(out)
            # else: PK table held, peer shares still in flight — the
            # handle_message_peer_shares completion triggers training
            return
        self._train_masked()

    def handle_message_pk_table(self, msg: Message) -> None:
        """PK table in: Shamir-share b_u and s_sk, encrypt share (u -> v)
        under the c-key agreement with v, ship through the server
        (reference ``__offline`` :272 + ``_send_secret_share_to_sever``)."""
        with self._lock:
            # Share-out must happen exactly once: re-sharing b_u/s_sk under a
            # FRESH random polynomial (e.g. on an MQTT redelivery of the PK
            # table) would leave peers holding shares of the same secret from
            # different polynomials — Shamir reconstruction then silently
            # yields garbage and the unmasked aggregate is wrong.
            if self._shared_out:
                return
            self._shared_out = True
        try:
            self._share_out(msg)
        except Exception:
            # the single send failed atomically — no peer holds shares yet, so
            # a redelivered PK table may safely retry with a fresh polynomial
            with self._lock:
                self._shared_out = False
            raise

    def _share_out(self, msg: Message) -> None:
        table = msg.get(MSG_ARG_KEY_PK_TABLE)
        self.pk_table = {int(u): (int(v[0]), int(v[1])) for u, v in table.items()}
        rng = np.random.RandomState(
            int.from_bytes(os.urandom(4), "little")
        )
        b_shares = shamir_share(self.b_u, self.n, self.t + 1, rng)
        sk_shares = shamir_share(self.s_sk, self.n, self.t + 1, rng)
        b_enc = np.zeros(self.n, dtype=np.int64)
        sk_enc = np.zeros(self.n, dtype=np.int64)
        for v in range(1, self.n + 1):
            pad_b, pad_sk = _share_pad(
                dh_agree(self.c_sk, self.pk_table[v][0]), self.rank, v
            )
            b_enc[v - 1] = (b_shares[v - 1][1] + pad_b) % P
            sk_enc[v - 1] = (sk_shares[v - 1][1] + pad_sk) % P
        out = Message(MSG_TYPE_C2S_SECRET_SHARES, self.rank, 0)
        out.add_params(MSG_ARG_KEY_B_SHARES, b_enc)
        out.add_params(MSG_ARG_KEY_SK_SHARES, sk_enc)
        self.send_message(out)

    def handle_message_peer_shares(self, msg: Message) -> None:
        b_enc = msg.get(MSG_ARG_KEY_B_SHARES)
        sk_enc = msg.get(MSG_ARG_KEY_SK_SHARES)
        with self._lock:
            for u_str, b in b_enc.items():
                u = int(u_str)
                pad_b, pad_sk = _share_pad(
                    dh_agree(self.c_sk, self.pk_table[u][0]), u, self.rank
                )
                self.held_shares[u] = (
                    (int(b) - pad_b) % P,
                    (int(sk_enc[u_str]) - pad_sk) % P,
                )
            ready = len(self.held_shares) == self.n
        if ready:
            self._setup_done.set()
            self._train_masked()

    # -- per round ------------------------------------------------------------
    def _train_masked(self) -> None:
        with self._lock:
            msg = self._pending_msg
            self._pending_msg = None
        if msg is None:
            return
        round_idx = int(msg.get(md.MSG_ARG_KEY_ROUND_INDEX))
        params = msg.get(md.MSG_ARG_KEY_MODEL_PARAMS)
        client_idx = int(msg.get(md.MSG_ARG_KEY_CLIENT_INDEX, self.rank - 1))
        new_vars, n_samples = self.trainer.train(params, round_idx, self.seed_key, client_idx)
        self.rounds_trained += 1
        flat, _ = jax.flatten_util.ravel_pytree(new_vars)
        peer_seeds = {
            v: derive_round_seed(dh_agree(self.s_sk, self.pk_table[v][1]), round_idx)
            for v in self.pk_table if v != self.rank
        }
        self_seed = derive_round_seed(self.b_u, round_idx)
        reply = Message(md.MSG_TYPE_C2S_SEND_MODEL_TO_SERVER, self.rank, 0)
        if self.stream:
            ring = self.ring
            if ring.codec == "qsgd8":
                # quantize-then-mask (ISSUE 15): qsgd8's stochastic grid at
                # the config-shared scale over the round's DELTA — small
                # values, int8 width, masked sum exactly decodable
                base_flat, _ = jax.flatten_util.ravel_pytree(params)
                delta = np.asarray(flat, np.float64) - np.asarray(base_flat, np.float64)
                q = secagg_stream.quantize_stochastic_int8(
                    delta, ring.frac_bits,
                    [int(self.cfg.random_seed), int(round_idx), int(self.rank)])
                x_field = np.mod(q, ring.modulus)
                is_delta = True
            else:
                x_field = quantize_to_field(np.asarray(flat), bits=self.q_bits)
                is_delta = False
            masked = secagg_stream.mask_vector(x_field, self.rank, peer_seeds,
                                               self_seed, ring.modulus)
            packed = secagg_stream.pack_ring(masked, ring.bits)
            codecs.note_masked_payload(
                f"secagg_{ring.codec}", packed.nbytes, np.asarray(flat).nbytes)
            reply.add_params(md.MSG_ARG_KEY_MODEL_PARAMS, packed)
            meta = ring.meta(int(x_field.size))
            meta["delta"] = is_delta
            reply.add_params(MSG_ARG_KEY_SECAGG_META, meta)
        else:
            x_field = quantize_to_field(np.asarray(flat), bits=self.q_bits)
            masked = masked_input(x_field, self.rank, peer_seeds, self_seed)
            reply.add_params(md.MSG_ARG_KEY_MODEL_PARAMS, masked)
        reply.add_params(md.MSG_ARG_KEY_NUM_SAMPLES, n_samples)
        reply.add_params(md.MSG_ARG_KEY_ROUND_INDEX, round_idx)
        self.send_message(reply)

    def handle_message_active_set(self, msg: Message) -> None:
        """Reveal b-shares of survivors, s_sk-shares of dropped — NEVER both
        for the same peer (reference ``handle_message_receive_active_from_
        server`` :134)."""
        active = {int(c) for c in msg.get(MSG_ARG_KEY_ACTIVE_SET)}
        with self._lock:
            b_rev = {str(u): y[0] for u, y in self.held_shares.items() if u in active}
            sk_rev = {str(u): y[1] for u, y in self.held_shares.items() if u not in active}
        reply = Message(MSG_TYPE_C2S_SHARE_REVEAL, self.rank, 0)
        reply.add_params(MSG_ARG_KEY_B_REVEALS, b_rev)
        reply.add_params(MSG_ARG_KEY_SK_REVEALS, sk_rev)
        reply.add_params(md.MSG_ARG_KEY_ROUND_INDEX, int(msg.get(md.MSG_ARG_KEY_ROUND_INDEX)))
        self.send_message(reply)


# -- builders -----------------------------------------------------------------

def build_sa_server(cfg, dataset, model, backend: Optional[str] = None) -> SAServerManager:
    from ..data.dataset import pad_eval_set

    eval_bs = min(256, max(32, cfg.test_batch_size))
    test_arrays = pad_eval_set(dataset.test_x, dataset.test_y, eval_bs)
    aggregator = SAAggregator(cfg, model, dataset.train_x[: cfg.batch_size], test_arrays)
    return SAServerManager(cfg, aggregator, backend=backend)


def build_sa_client(cfg, dataset, model, rank: int, backend: Optional[str] = None) -> SAClientManager:
    ix = dataset.client_idx[rank - 1]
    trainer = FedMLTrainer(cfg, model, dataset.train_x[ix], dataset.train_y[ix])
    return SAClientManager(cfg, trainer, rank=rank, backend=backend)


def run_shamir_secagg_process_group(cfg, dataset, model, backend: str = "INPROC",
                                    timeout: float = 600.0, drop_ranks: frozenset = frozenset()):
    """1 server + N Shamir-SecAgg clients on threads over the in-proc fabric.
    ``drop_ranks`` clients complete setup (their pair masks ARE in survivors'
    uploads) but never upload a model — the hard dropout case requiring
    s_sk reconstruction."""
    from ..comm.inproc import InProcRouter

    InProcRouter.reset(str(getattr(cfg, "run_id", "0")))
    clients = []
    for r in range(1, cfg.client_num_in_total + 1):
        c = build_sa_client(cfg, dataset, model, rank=r, backend=backend)
        if r in drop_ranks:
            c._train_masked = lambda: None  # drops out before model upload
        clients.append(c)
    for c in clients:
        c.run_in_thread()
    server = build_sa_server(cfg, dataset, model, backend=backend)
    try:
        history = server.run_until_done(timeout=timeout)
    finally:
        for c in clients:
            c.finish()
    return history, server
