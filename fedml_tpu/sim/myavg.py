"""MyAvg — CKA layer-selective personalized aggregation (fork research).

Re-implements the fork's research algorithm family (reference
``my_research/sp_fedavg_cifar10_resnet20_example/MyAvgAPI_7.py``; dispatched by
``python/fedml/simulation/simulator.py:88-95`` as ``MyAgg-*``):

- **Personalized clients** (``MyAvgAPI_7.py:289-292``, ``set_param=False``):
  every client keeps its OWN model across rounds; local SGD starts from the
  personal weights, never from the global model.
- **Mod-N round-interval layer schedule** (``MyAvgAPI_7.py:242-263``): each
  round a substring :class:`LayerFilter` decides WHICH layers aggregate.  On
  rounds divisible by an ``agg_mod_list`` entry (first match wins, round 0
  exempt) the filter from ``agg_mod_dict[mod]`` applies; otherwise the default
  ``agg_*_layer`` filter.  Unaggregated layers stay local to each client.
- **CKA top-k partner aggregation** (``MyAvgAPI_7.py:364-435`` +
  ``my_utils.py:61-74``): for layers selected by the ``cka_*_layer`` filter,
  each client aggregates a layer only over its ``cka_select_topk`` most
  CKA-similar peers (linear CKA over the clients' layer DELTAS, conv kernels
  mean-pooled over their spatial dims; self always included; similarities
  outside ``[cka_low_thresh, cka_high_thresh]`` dropped).  For >=2-D layers
  the partner-averaged delta is corrected against the global-average delta:
  when their inner product is negative the conflicting component is projected
  out, and the result is rescaled to the mean of the two norms
  (``MyAvgAPI_7.py:410-434``; the reference's ``trace``/``dot`` forms are the
  Frobenius inner product on 2-D weights — used here for every >=2-D leaf).
- The **server model** takes the plain sample-weighted average of the
  aggregated layers (``g_all_global``), serving as the evaluation model.

TPU-native design: there is no per-round Python filtering.  The layer filters
compile to per-leaf {0,1} mask TABLES indexed by a round-derived config id, so
the whole round — personal local SGD (vmapped over the ``clients`` mesh axis),
CKA matrices, top-k partner selection (``lax.top_k``), masked weighted means —
is ONE jitted function of ``round_idx``, scan-compatible with
``MeshSimulator.run_rounds`` (the reference recomputes filters and loops
layers in Python every round).
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import constants as C
from ..core import pytree as pt, rng
from ..fl.local_sgd import make_eval_fn
from ..parallel import mesh as meshlib
from .engine import REAL_COUNT_KEY, MeshSimulator


class LayerFilter:
    """Substring layer selection — semantics of ``my_utils.py:13-44``.

    A dotted leaf path is kept iff it contains NO ``unselect`` key, ALL
    ``all_select`` keys, and (if any given) at least one ``any_select`` key.
    An entirely empty filter keeps everything.
    """

    def __init__(self, unselect: Sequence[str] = (), all_select: Sequence[str] = (),
                 any_select: Sequence[str] = ()):
        self.unselect = tuple(unselect or ())
        self.all_select = tuple(all_select or ())
        self.any_select = tuple(any_select or ())

    def __call__(self, path: str) -> bool:
        if not (self.unselect or self.all_select or self.any_select):
            return True
        return (
            all(k not in path for k in self.unselect)
            and all(k in path for k in self.all_select)
            and (not self.any_select or any(k in path for k in self.any_select))
        )

    def __repr__(self):
        return (f"LayerFilter(unselect={self.unselect}, "
                f"all={self.all_select}, any={self.any_select})")


def leaf_paths(tree) -> list[str]:
    """Dotted path per leaf, e.g. ``params.conv1.kernel`` — the name the
    substring filters match against (reference filters match torch state_dict
    keys; configs supply their own substrings either way)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, _leaf in flat:
        parts = []
        for p in path:
            if hasattr(p, "key"):
                parts.append(str(p.key))
            elif hasattr(p, "idx"):
                parts.append(str(p.idx))
            else:
                parts.append(str(p))
        out.append(".".join(parts))
    return out


def _as_rows(x: jax.Array) -> jax.Array:
    """Reduce one client's layer delta to the 2-D matrix CKA runs on
    (``my_utils.py:68-71``): convs are mean-pooled over spatial dims and
    oriented rows=output-features (torch OIHW ``mean(dim=[-1,-2])`` ==
    flax HWIO ``mean(axis=spatial)`` transposed); 1-D leaves become a
    column vector."""
    if x.ndim == 0:
        return x.reshape(1, 1)
    if x.ndim == 1:
        return x[:, None]
    if x.ndim == 2:
        return x.T  # flax [in, out] -> rows = out (torch [out, in] parity)
    spatial = tuple(range(x.ndim - 2))
    return x.mean(axis=spatial).T  # [in, out] -> [out, in]


def linear_cka_matrix(deltas: jax.Array) -> jax.Array:
    """Pairwise linear CKA over ``m`` clients' reduced layer matrices.

    ``deltas``: [m, r, c].  Returns [m, m] with 1s on the diagonal, clipped to
    <= 1 (``my_utils.py:72-73``).  Linear-kernel CKA with the centered-HSIC
    normalization of ``my_utils.py:185-212``: with Kc = H X Xt H,
    CKA(i, j) = <Kc_i, Kc_j> / (||Kc_i|| ||Kc_j||) — the 1/(n-1)^2 factors
    cancel.  Computed for ALL pairs as one Gram matmul instead of the
    reference's O(m^2) Python loop.
    """
    m, r, _ = deltas.shape
    x = deltas.astype(jnp.float32)
    k = jnp.einsum("mrc,msc->mrs", x, x)  # per-client kernel [m, r, r]
    # center: H K H with H = I - 11^T/r
    k = k - k.mean(axis=1, keepdims=True)
    k = k - k.mean(axis=2, keepdims=True)
    flat = k.reshape(m, r * r)
    gram = flat @ flat.T  # <Kc_i, Kc_j>
    diag = jnp.sqrt(jnp.clip(jnp.diagonal(gram), 0.0))
    denom = diag[:, None] * diag[None, :]
    cka = jnp.where(denom > 0, gram / jnp.where(denom > 0, denom, 1.0), 0.0)
    # degenerate (zero-delta) clients: fall back to self-similarity only
    cka = jnp.where(jnp.eye(m, dtype=bool), 1.0, cka)
    return jnp.minimum(cka, 1.0)


class MyAvgSimulator(MeshSimulator):
    """MeshSimulator with the MyAvg server path.

    ``client_states`` holds every client's personal model (stacked, sharded on
    the ``clients`` axis); the jitted round trains the sampled clients from
    their personal weights, then rebuilds both the server model and each
    sampled client's personal model per the mask tables + CKA selection.
    """

    def __init__(self, cfg, dataset, model, mesh=None, logger=None):
        if cfg.backend_sim == C.SIMULATION_BACKEND_SP:
            raise NotImplementedError(
                "MyAvg runs as the mesh round program; the sequential SP twin "
                "is not provided for it (set backend_sim='MESH')"
            )
        active_trust = [
            f for f in ("enable_secagg", "enable_fhe", "enable_contribution")
            if getattr(cfg, f, False)
        ]
        if active_trust:
            # secagg/fhe change the aggregation PROTOCOL (masked/encrypted
            # sums are incompatible with per-leaf CKA personalization, which
            # needs individual client deltas in the clear) and contribution
            # replay assumes the FedAvg server path — refuse loudly.
            # Attacks, defenses, and DP compose: the MyAvg round routes its
            # stacked trained models through the same trust hooks as the
            # engine round (round-3 verdict item 9).
            raise NotImplementedError(
                f"trust features {active_trust} are not wired into the MyAvg "
                "round; use a FedAvg-family optimizer for them"
            )
        orig_name = cfg.federated_optimizer
        # local training is plain client SGD (the reference's MyTrainer_7 is
        # the stock classification trainer, MyAvgAPI_7.py:16-70); the MyAvg
        # logic is all server-side
        cfg = dataclasses.replace(cfg, federated_optimizer=C.FEDERATED_OPTIMIZER_FEDAVG)
        super().__init__(cfg, dataset, model, mesh=mesh, logger=logger)
        # cfg must keep reporting the real optimizer to logging/bookkeeping
        self.cfg = dataclasses.replace(self.cfg, federated_optimizer=orig_name)
        if self.trust is not None and self.trust.defense is not None:
            from ..trust.defense.base import Defense

            if type(self.trust.defense).on_agg is not Defense.on_agg:
                # an aggregation-REPLACING defense (krum/median/bulyan/...)
                # collapses the m client deltas to one aggregate, which
                # destroys exactly the per-client structure the CKA partner
                # selection personalizes from — only transforming defenses
                # (clipping, reweighting, filtering via before()) compose
                raise NotImplementedError(
                    f"defense {type(self.trust.defense).name!r} replaces the "
                    "aggregation (on_agg); MyAvg needs per-client deltas — "
                    "use a transforming defense (e.g. norm_diff_clipping, "
                    "weak_dp, foolsgold) or a FedAvg-family optimizer"
                )

        n = self._n_pad  # engine pads the client axis to the mesh multiple
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), self.global_vars
        )
        self.client_states = meshlib.shard_leading_axis(stacked, self.mesh)

        # per-client test shards (LEAF-style test_client_idx): personalized
        # eval must score each personal model on ITS OWN conditional — under
        # client-dependent class conditionals the union test set would punish
        # exactly the specialization MyAvg optimizes
        self._personal_test = None
        if dataset.test_client_idx is not None:
            eval_bs = self._eval_bs
            caps = [len(ix) for ix in dataset.test_client_idx]
            empty = [i for i, c in enumerate(caps) if c == 0]
            if empty:
                # an empty shard would silently score 0.0 and collapse the
                # min-accuracy headline metric into noise
                raise ValueError(
                    f"clients {empty} have EMPTY per-client test shards; "
                    "personalized eval needs at least one test sample per "
                    "client (raise synthetic_test_size or fix test_client_idx)"
                )
            cap = meshlib.round_up(max(max(caps), 1), eval_bs)
            tx = np.zeros((len(caps), cap) + dataset.test_x.shape[1:], dataset.test_x.dtype)
            ty = np.zeros((len(caps), cap) + dataset.test_y.shape[1:], dataset.test_y.dtype)
            for i, ix in enumerate(dataset.test_client_idx):
                reps = np.resize(ix, cap)  # cyclic pad; n_valid masks the rest
                tx[i], ty[i] = dataset.test_x[reps], dataset.test_y[reps]
            self._personal_test = (
                jnp.asarray(tx), jnp.asarray(ty),
                jnp.asarray(caps, jnp.int32),
            )

        # ---- static mask tables -------------------------------------------
        paths = leaf_paths(self.global_vars)
        self._paths = paths
        default_f = LayerFilter(cfg.agg_unselect_layer, cfg.agg_all_select_layer,
                                cfg.agg_any_select_layer)
        self._mods = [int(mi) for mi in cfg.agg_mod_list]
        if any(mi <= 0 for mi in self._mods):
            # a 0 would trace round_idx % 0 into XLA (undefined, silent)
            raise ValueError(f"agg_mod_list entries must be positive, got {self._mods}")
        mod_filters = []
        for mi in self._mods:
            spec = cfg.agg_mod_dict.get(mi, cfg.agg_mod_dict.get(str(mi), {}))
            mod_filters.append(LayerFilter(
                spec.get("agg_unselect_layer", ()),
                spec.get("agg_all_select_layer", ()),
                spec.get("agg_any_select_layer", ()),
            ))
        filters = [default_f] + mod_filters  # config id 0 = default
        # [n_leaves, n_configs] 0/1 — which leaves aggregate under which config
        self._agg_table = [
            jnp.asarray([1.0 if f(p) else 0.0 for f in filters], jnp.float32)
            for p in paths
        ]
        cka_f = LayerFilter(cfg.cka_unselect_layer, cfg.cka_all_select_layer,
                            cfg.cka_any_select_layer)
        self._cka_flags = [bool(cka_f(p)) for p in paths]
        # filters come from hand-mapped torch state_dict substrings; a typo
        # (or a flax-vs-torch naming mismatch) silently degenerates MyAvg to
        # plain FedAvg — every configured substring must match SOME leaf,
        # and a configured CKA filter must select at least one leaf
        all_subs = set(cfg.agg_unselect_layer) | set(cfg.agg_all_select_layer) \
            | set(cfg.agg_any_select_layer) | set(cfg.cka_unselect_layer) \
            | set(cfg.cka_all_select_layer) | set(cfg.cka_any_select_layer)
        for spec in cfg.agg_mod_dict.values():
            for key in ("agg_unselect_layer", "agg_all_select_layer", "agg_any_select_layer"):
                all_subs |= set(spec.get(key, ()))
        dead = sorted(s for s in all_subs if not any(s in p for p in paths))
        if dead:
            raise ValueError(
                f"MyAvg layer-filter substrings {dead} match NO model leaf "
                f"path; known paths: {paths}"
            )
        cka_configured = bool(cfg.cka_any_select_layer or cfg.cka_all_select_layer
                              or cfg.cka_unselect_layer)
        if cka_configured and not any(self._cka_flags):
            raise ValueError(
                "cka_*_select_layer is configured but selects zero leaves — "
                "the CKA personalization would silently never run"
            )
        self._topk = int(cfg.cka_select_topk)
        self._thresh = (float(cfg.cka_low_thresh), float(cfg.cka_high_thresh))
        # rebuild the jitted round over the override (the parent compiled the
        # plain FedAvg round before these tables existed)
        self._round_fn = jax.jit(self._make_round_fn())
        self._multi_round_fns = {}

    # ------------------------------------------------------------------
    def _eval_lanes(self) -> int:
        # evaluate_personalized vmaps make_eval_fn over every real client's
        # model at the engine's batch: a step scores lanes x batch samples
        return self._n_real

    def _config_id(self, round_idx):
        """First ``agg_mod_list`` entry dividing ``round_idx`` wins; round 0
        always uses the default filter (``MyAvgAPI_7.py:242-247``)."""
        cid = jnp.int32(0)
        for i in reversed(range(len(self._mods))):
            cid = jnp.where(round_idx % self._mods[i] == 0, jnp.int32(i + 1), cid)
        return jnp.where(round_idx == 0, jnp.int32(0), cid)

    # ------------------------------------------------------------------
    def _make_round_fn(self):
        if not hasattr(self, "_agg_table"):
            # parent __init__ jits a round before the mask tables exist; that
            # placeholder is discarded and rebuilt at the end of __init__
            return super()._make_round_fn()
        algo = self.algorithm
        cfg = self.cfg
        n_total = self.dataset.n_clients
        m = min(cfg.client_num_per_round, n_total)
        k_sel = min(self._topk, m)
        lo, hi = self._thresh
        agg_table = self._agg_table
        cka_flags = self._cka_flags
        treedef = jax.tree_util.tree_structure(self.global_vars)

        def partner_select(cka_row, i, weights):
            """Top-k + threshold partner weights for client i's row
            (``MyAvgAPI_7.py:398-408``): self always kept, subset re-weighted
            by sample counts."""
            _, top_idx = jax.lax.top_k(cka_row, k_sel)
            in_topk = jnp.zeros_like(cka_row).at[top_idx].set(1.0)
            ok = in_topk * (cka_row >= lo) * (cka_row <= hi)
            ok = ok.at[i].set(1.0)
            pw = weights * ok
            return pw / jnp.maximum(pw.sum(), 1e-12)

        m_pad = meshlib.round_up(m, self._lane_multiple)

        def round_fn(global_vars, server_state, client_states, counts, data_x,
                     data_y, round_idx, key, prev_delta):
            sampled = rng.sample_clients(key, round_idx, n_total, m)
            xs, ys, cnts, personal, rkey, keys = self._gather_round_inputs(
                sampled, m, m_pad, counts, data_x, data_y, client_states, key, round_idx
            )

            def one_client(pvars, x, y, cnt, k):
                out = algo.client_update(pvars, None, server_state, x, y, cnt, k)
                return out.contribution, out.metrics
            trained, metrics = jax.vmap(one_client)(personal, xs, ys, cnts, keys)
            # pad lanes carry client 0's redundant training — drop them so the
            # CKA gram, partner selection and aggregation stay exactly m x m
            trained = self._slice_lanes(trained, m)
            metrics = self._slice_lanes(metrics, m)

            weights = cnts[:m].astype(jnp.float32)
            # the clients' RETAINED local models: trust hooks transform only
            # the SHIPPED copy (LDP noise / defense clipping applies to the
            # transmitted update, never to client-local state — otherwise a
            # personal head that never aggregates would random-walk under a
            # fresh noise draw every sampled round)
            retained = trained
            if self.trust is not None:
                # same hook chain as the engine round (attack simulation +
                # LDP on the stacked trained models; defense before()
                # transforms deltas / reweights — the reweighted weights flow
                # into BOTH the global aggregate and the CKA partner weights,
                # so a zero-weighted byzantine client also loses its vote as
                # a personalization partner)
                trained, weights = self.trust.on_client_outputs(
                    trained, weights, sampled, global_vars, rkey
                )
                trained, weights, agg_override = self.trust.on_aggregation(
                    trained, weights, global_vars, rkey, prev_delta=prev_delta
                )
                if agg_override is not None:
                    # normally refused at __init__ (on_agg check); a pipeline
                    # installed post-construction must hit the same wall —
                    # silently discarding a defense's aggregate is worse
                    raise NotImplementedError(
                        "trust pipeline returned an aggregation override; "
                        "MyAvg needs per-client deltas (see __init__ refusal)"
                    )
            wnorm = weights / jnp.maximum(weights.sum(), 1e-12)
            cid = self._config_id(round_idx)

            g_leaves = jax.tree_util.tree_leaves(global_vars)
            t_leaves = jax.tree_util.tree_leaves(trained)
            r_leaves = jax.tree_util.tree_leaves(retained)
            new_g_leaves, new_p_leaves = [], []
            for li, (g, t, t_clean) in enumerate(zip(g_leaves, t_leaves, r_leaves)):
                agg_on = jnp.take(agg_table[li], cid)  # {0,1} this round
                delta = (t - g[None]).astype(jnp.float32)
                bshape = (m,) + (1,) * g.ndim
                g_all = jnp.tensordot(wnorm, delta, axes=1)  # weighted mean
                new_g = (g + agg_on * g_all).astype(g.dtype)

                if cka_flags[li] and g.ndim > 0:
                    def cka_personalize(delta, g_all, g=g):
                        rows = jax.vmap(_as_rows)(delta)
                        cka = linear_cka_matrix(rows)
                        pw = jax.vmap(partner_select, in_axes=(0, 0, None))(
                            cka, jnp.arange(m), weights
                        )  # [m, m] partner weights per client
                        g_cka = jnp.tensordot(pw, delta, axes=1)  # [m, ...]
                        if g.ndim >= 2:
                            # negative-projection correction + norm rescale
                            # (MyAvgAPI_7.py:410-434)
                            axes = tuple(range(1, g.ndim + 1))
                            a_n = jnp.sqrt((g_cka ** 2).sum(axis=axes))
                            gl_n = jnp.sqrt((g_all ** 2).sum())
                            a_hat = g_cka / jnp.maximum(a_n, 1e-12).reshape(bshape)
                            g_hat = g_all / jnp.maximum(gl_n, 1e-12)
                            b = (a_hat * g_hat[None]).sum(axis=axes)
                            a_opt = jnp.where(
                                (b < 0).reshape(bshape),
                                a_hat - b.reshape(bshape) * g_hat[None], a_hat,
                            )
                            g_cka = a_opt * ((a_n + gl_n) / 2.0).reshape(bshape)
                        return g_cka

                    # the result is discarded on rounds where the layer is
                    # gated off (agg_on == 0) — skip the gram/top-k work then
                    pers_delta = jax.lax.cond(
                        agg_on > 0, cka_personalize,
                        lambda d, a: jnp.zeros((m,) + g.shape, jnp.float32),
                        delta, g_all,
                    )
                else:
                    pers_delta = jnp.broadcast_to(g_all[None], (m,) + g.shape)

                # aggregated layers: personal <- old global + personalized
                # delta (server-computed from the SHIPPED updates — trust
                # transforms legitimately flow in here); unaggregated: the
                # client keeps its CLEAN locally trained leaf (strict=False
                # load semantics, MyAvgAPI_7.py:320-326)
                new_p = jnp.where(agg_on > 0, (g[None] + pers_delta).astype(t.dtype), t_clean)
                new_g_leaves.append(new_g)
                new_p_leaves.append(new_p)

            new_global = jax.tree_util.tree_unflatten(treedef, new_g_leaves)
            if self.trust is not None:
                # CDP clip+noise and defense post-processing on the GLOBAL
                # model only — personal models are the clients' own local
                # state and never leave the device in this simulation
                new_global = self.trust.on_after_aggregation(new_global, global_vars, rkey)
            new_personal = jax.tree_util.tree_unflatten(treedef, new_p_leaves)
            new_states = jax.tree_util.tree_map(
                lambda full, upd: full.at[sampled].set(upd.astype(full.dtype)),
                client_states, new_personal,
            )
            new_delta = prev_delta
            if prev_delta is not None:  # cross-round defense history
                new_flat, _ = pt.tree_flatten_to_vector(new_global)
                old_flat, _ = pt.tree_flatten_to_vector(global_vars)
                new_delta = new_flat - old_flat
            round_metrics = {k: jnp.mean(v) for k, v in metrics.items()}
            round_metrics["myavg_config_id"] = cid.astype(jnp.float32)
            round_metrics[REAL_COUNT_KEY] = jnp.sum(cnts[:m])  # as the engine's round
            return new_global, server_state, new_states, new_delta, round_metrics

        return round_fn

    # ------------------------------------------------------------------
    def evaluate(self) -> dict:
        """Global-model eval PLUS personalized-model eval: the personal
        models are what MyAvg optimizes (the reference's periodic test is
        per-client local models, ``MyAvgAPI_7.py:304-309``), so the run-loop
        history must carry both."""
        out = super().evaluate()
        out.update(self.evaluate_personalized())
        return out

    def evaluate_personalized(self) -> dict:
        """Mean/min test accuracy of the clients' PERSONAL models — the
        quantity MyAvg optimizes (the reference evaluates every client's local
        model, ``MyAvgAPI_7.py:487-520``).  With per-client test shards
        (``test_client_idx``) each personal model is scored on its own
        conditional; otherwise on the shared test set."""
        # pad rows hold untrained init weights — evaluate real clients only
        # (the min over clients would otherwise report the dummy rows)
        states = self._slice_lanes(self.client_states, self._n_real)
        if self._personal_test is not None:
            if getattr(self, "_personal_eval_fn_pc", None) is None:
                self._personal_eval_fn_pc = jax.jit(jax.vmap(
                    make_eval_fn(self.model, self.hp, batch_size=self._eval_bs),
                    in_axes=(0, 0, 0, 0),
                ))
            res = self._personal_eval_fn_pc(states, *self._personal_test)
        else:
            if getattr(self, "_personal_eval_fn", None) is None:
                self._personal_eval_fn = jax.jit(jax.vmap(
                    make_eval_fn(self.model, self.hp, batch_size=self._eval_bs),
                    in_axes=(0, None, None, None),
                ))
            res = self._personal_eval_fn(states, *self._test)
        return {
            "personalized_test_acc_mean": float(jnp.mean(res["test_acc"])),
            "personalized_test_acc_min": float(jnp.min(res["test_acc"])),
        }
