"""Plain float32 reference of FedAvg rounds of the CIFAR ResNet-20.

Follows, in straightforward ``jax.numpy``/``lax.conv`` at ``highest``
precision and with host loops (no vmap, no scan, no mesh, nothing imported
from the program): the round's client sampling, each client's batch order,
local SGD with batch normalisation in training mode and steps beyond a
client's own budget left out (``step_mode=match``), the sample-weighted mean
of the clients' variables (running statistics included), and the evaluation
on the test set in inference mode.  The random streams are the program's
published discipline, written out: root key from ``round_seed``, ``fold_in`` by
round, by the client tag and id, by epoch.

Departures from the published recipe (He et al. ResNet-20 on CIFAR-10,
FedML's ``sp_fedavg_cifar10_resnet20_example``): the images are a synthetic
stand-in from the seed (class prototypes plus noise, values exact in
bfloat16), shortcuts are the parameter-free option A, and the weights are the
benchmark's own draw from the seed.

``control="fp8"`` rounds the operands of every convolution and of the
classifier to float8_e4m3 (per-tensor absmax scale, straight-through
gradient): the precision step below the configuration's bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

CLIENT_TAG = 0x636C69
BN_MOMENTUM, BN_EPS = 0.9, 1e-5
E4M3_MAX = 448.0
HI = jax.lax.Precision.HIGHEST


def round_seed(t: dict, seed: int) -> int:
    """The program's ``random_seed``: every round's cohort and each client's
    batch order.  A traffic file that names ``round_seed`` gives every
    ``--seed`` the same cohorts, so the same work in a window, while the
    images, labels and weights still come from ``--seed``."""
    return t.get("round_seed", seed) % (2 ** 31)


# ------------------------------------------------------------------- data
def make_data(t: dict, seed: int) -> dict:
    """Images, labels and the clients' index lists.  The partition (sizes and
    label skew) comes from the traffic file's ``partition_seed`` over class
    slots, so capacity, step counts and compiled shapes are the same on every
    ``--seed``; which class fills which slot, the prototypes, the noise and
    the test set come from ``--seed``.  Values are exact in bfloat16."""
    import ml_dtypes

    n, n_test, classes = t["train_samples"], t["test_samples"], t["classes"]
    shape = tuple(t["image_shape"])
    slots = np.arange(n) % classes
    clients = partition(t, slots)
    g = np.random.default_rng([seed, 1])
    relabel = g.permutation(classes)
    proto = g.standard_normal((classes,) + shape, dtype=np.float32)
    y = relabel[slots].astype(np.int32)
    y_test = g.integers(0, classes, size=n_test).astype(np.int32)

    def images(labels):
        x = g.standard_normal((labels.shape[0],) + shape, dtype=np.float32)
        x += t["signal"] * proto[labels]
        return x.astype(ml_dtypes.bfloat16)

    return {"train_x": images(y), "train_y": y, "test_x": images(y_test), "test_y": y_test,
            "clients": clients, "classes": classes}


def partition(t: dict, slots: np.ndarray) -> list[np.ndarray]:
    """``homo``: a permutation cut into equal shards.  ``hetero``: per class
    Dirichlet(alpha) proportions over the clients, clients already holding
    their share zeroed, drawn again until the smallest shard has 10 (the
    partition FedML's CIFAR-10 loader uses)."""
    n, k = slots.shape[0], t["clients_total"]
    g = np.random.RandomState(t["partition_seed"])
    if t["partition_method"] == "homo":
        return [np.sort(p) for p in np.array_split(g.permutation(n), k)]
    if t["partition_method"] != "hetero":
        raise ValueError(f"unknown partition_method {t['partition_method']!r}")
    for _ in range(1000):
        shards: list[list[int]] = [[] for _ in range(k)]
        for c in np.unique(slots):
            idx = np.where(slots == c)[0]
            g.shuffle(idx)
            p = g.dirichlet(np.repeat(t["partition_alpha"], k))
            p = np.array([q * (len(s) < n / k) for q, s in zip(p, shards)])
            cuts = (np.cumsum(p / p.sum()) * len(idx)).astype(int)[:-1]
            for s, part in zip(shards, np.split(idx, cuts)):
                s.extend(part.tolist())
        if min(len(s) for s in shards) >= 10:
            return [np.sort(np.array(s, dtype=np.int64)) for s in shards]
    raise RuntimeError("the Dirichlet partition never reached 10 samples a client")


def capacity(clients: list[np.ndarray], batch: int) -> int:
    biggest = max(len(c) for c in clients)
    return -(-biggest // batch) * batch


# ---------------------------------------------------------------- weights
def leaf_shapes(c: dict) -> dict[str, tuple]:
    ch, nb, classes = c["channels"], c["blocks_per_stage"], c["num_classes"]
    s = {"params/Conv_0/kernel": (3, 3, 3, ch[0])}

    def bn(prefix, width):
        s[f"params/{prefix}/scale"] = s[f"params/{prefix}/bias"] = (width,)
        s[f"batch_stats/{prefix}/mean"] = s[f"batch_stats/{prefix}/var"] = (width,)

    bn("BatchNorm_0", ch[0])
    cin, i = ch[0], 0
    for width in ch:
        for _ in range(nb):
            b = f"BasicBlock_{i}"
            s[f"params/{b}/Conv_0/kernel"] = (3, 3, cin, width)
            s[f"params/{b}/Conv_1/kernel"] = (3, 3, width, width)
            bn(f"{b}/BatchNorm_0", width)
            bn(f"{b}/BatchNorm_1", width)
            cin, i = width, i + 1
    s["params/Dense_0/kernel"], s["params/Dense_0/bias"] = (cin, classes), (classes,)
    return s


def init_weights(c: dict, seed: int) -> dict:
    """He-normal kernels, batch-norm scales and biases jittered about 1 and
    0 (so that every leaf has a gradient of its own), running mean 0 and
    variance 1; float32, one jitted call.  The key is an argument, so every
    seed runs the one compiled program."""
    shapes = leaf_shapes(c)

    def make(key):
        out = {}
        for i, name in enumerate(sorted(shapes)):
            k, shp = jax.random.fold_in(key, i), shapes[name]
            if name.endswith("kernel"):
                fan_in = math.prod(shp[:-1])
                out[name] = jax.random.normal(k, shp, jnp.float32) * math.sqrt(2.0 / fan_in)
            elif name.endswith("/scale"):
                out[name] = 1.0 + 0.1 * jax.random.normal(k, shp, jnp.float32)
            elif name.endswith("/bias"):
                out[name] = 0.1 * jax.random.normal(k, shp, jnp.float32)
            elif name.endswith("/var"):
                out[name] = jnp.ones(shp, jnp.float32)
            else:
                out[name] = jnp.zeros(shp, jnp.float32)
        return out

    key = jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)), 7 + seed // (2 ** 31))
    return jax.jit(make)(key)


def change_norms(w: dict, w0: dict) -> dict[str, float]:
    out = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(a[k] - b[k]))) for k in a})(w, w0)
    return {k: float(v) for k, v in out.items()}


# ------------------------------------------------------------------ model
def _fake_fp8(x):
    s = jnp.max(jnp.abs(x)) / E4M3_MAX + 1e-30
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def conv3x3(x, k, stride: int):
    """3x3 convolution, padding SAME, as one matrix product over the nine
    shifted views of the padded input (NHWC x HWIO).  Written as a product
    because XLA:TPU takes over half an hour to compile this net's
    convolution gradients at ``highest`` precision, and a minute for
    products.  SAME pads (1, 1) at stride 1 and (0, 1) at stride 2 on even
    sizes, as ``lax.conv`` does."""
    n, h, wd, cin = x.shape
    out = h // stride
    lo = 1 if stride == 1 else 0
    xp = jnp.pad(x, ((0, 0), (lo, 1), (lo, 1), (0, 0)))
    views = [xp[:, i:i + stride * (out - 1) + 1:stride, j:j + stride * (out - 1) + 1:stride, :]
             for i in range(3) for j in range(3)]
    patches = jnp.concatenate(views, axis=-1).reshape(n * out * out, 9 * cin)
    return jnp.dot(patches, k.reshape(9 * cin, -1), precision=HI).reshape(n, out, out, -1)


def forward(w: dict, x, c: dict, train: bool, control=None):
    """Logits, and the variables with running statistics moved (training)."""
    q8 = _fake_fp8 if control == "fp8" else (lambda t: t)
    new = dict(w)

    def conv(x, name, stride=1):
        return conv3x3(q8(x), q8(w[f"params/{name}/kernel"]), stride)

    def bn(x, name):
        if train:
            mean = jnp.mean(x, (0, 1, 2))
            var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
            for stat, val in (("mean", mean), ("var", var)):
                key = f"batch_stats/{name}/{stat}"
                new[key] = BN_MOMENTUM * w[key] + (1 - BN_MOMENTUM) * val
        else:
            mean, var = w[f"batch_stats/{name}/mean"], w[f"batch_stats/{name}/var"]
        return ((x - mean) * jax.lax.rsqrt(var + BN_EPS) * w[f"params/{name}/scale"]
                + w[f"params/{name}/bias"])

    x = jax.nn.relu(bn(conv(x, "Conv_0"), "BatchNorm_0"))
    i = 0
    for stage, width in enumerate(c["channels"]):
        for b in range(c["blocks_per_stage"]):
            stride = 2 if (stage > 0 and b == 0) else 1
            p = f"BasicBlock_{i}"
            y = jax.nn.relu(bn(conv(x, f"{p}/Conv_0", stride), f"{p}/BatchNorm_0"))
            y = bn(conv(y, f"{p}/Conv_1"), f"{p}/BatchNorm_1")
            if x.shape != y.shape:  # option A: subsample, zero-pad the channels
                x = x[:, ::stride, ::stride, :]
                pad = width - x.shape[-1]
                x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (pad // 2, pad - pad // 2)))
            x = jax.nn.relu(y + x)
            i += 1
    x = jnp.mean(x, (1, 2))
    logits = jnp.dot(q8(x), q8(w["params/Dense_0/kernel"]), precision=HI) + w["params/Dense_0/bias"]
    return logits, new


def _ce(logits, y):
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.take_along_axis(logp, y[:, None], -1)[:, 0]


class ReferenceFedAvg:
    def __init__(self, c: dict, t: dict, seed: int, data: dict, control=None, fault=None):
        self.c, self.t, self.seed, self.data, self.fault = c, t, seed, data, fault
        self.w0 = init_weights(c, seed)
        self.w = dict(self.w0)
        self.round_idx = 0
        self.root = jax.random.PRNGKey(round_seed(t, seed))
        self.bsz = t["batch_size"]
        self.cap = capacity(data["clients"], self.bsz)
        self.spe = self.cap // self.bsz
        # the data lives on the device in float32; a step is handed the row
        # numbers of its batch, not the rows
        self.train_x = jnp.asarray(np.asarray(data["train_x"], np.float32))
        self.train_y = jnp.asarray(data["train_y"])
        self.test_x = np.asarray(data["test_x"], np.float32)
        lr = t["learning_rate"]

        def sgd(w, all_x, all_y, rows):
            x, y = all_x[rows], all_y[rows]

            def loss_fn(params, stats):
                logits, new = forward({**params, **stats}, x, c, True, control)
                return jnp.mean(_ce(logits, y)), new
            params = {k: v for k, v in w.items() if k.startswith("params/")}
            stats = {k: v for k, v in w.items() if not k.startswith("params/")}
            (loss, new), g = jax.value_and_grad(loss_fn, has_aux=True)(params, stats)
            return {**new, **{k: params[k] - lr * g[k] for k in params}}, loss

        self._sgd = jax.jit(sgd)
        # the sample-weighted mean, folded client by client in float32
        self._scale = jax.jit(lambda w, a: {k: a * v for k, v in w.items()})
        self._add_scaled = jax.jit(lambda t, w, a: {k: t[k] + a * w[k] for k in t})

        def evaluate(w, x, y):
            logits, _ = forward(w, x, c, False, control)
            return jnp.sum(_ce(logits, y)), jnp.sum(jnp.argmax(logits, -1) == y)

        self._eval = jax.jit(evaluate)

    def round(self) -> float:
        """One round; returns its train loss (mean over clients of the mean
        over each client's own steps)."""
        t, r = self.t, self.round_idx
        n, m = t["clients_total"], min(t["clients_per_round"], t["clients_total"])
        rkey = jax.random.fold_in(self.root, r)
        sampled = (np.arange(n) if n <= m
                   else np.asarray(jax.random.permutation(rkey, n))[:m])
        counts = np.array([len(self.data["clients"][i]) for i in sampled], np.float64)
        total, losses = None, []
        for cid, cnt in zip(sampled, counts):
            rows = np.resize(self.data["clients"][int(cid)], self.cap)
            key = jax.random.fold_in(jax.random.fold_in(rkey, CLIENT_TAG), int(cid))
            w, own, client_losses = self.w, -(-int(cnt) // self.bsz), []
            for e in range(t["epochs"]):
                perm = np.asarray(jax.random.permutation(
                    jax.random.fold_in(jax.random.fold_in(key, e), 1), self.cap))
                for s in range(self.spe):
                    if e * self.spe + s >= t["epochs"] * own:
                        continue
                    start = min(s * self.bsz, self.cap - self.bsz)
                    idx = rows[perm[start:start + self.bsz]]
                    if self.fault == "half_batch":
                        idx = idx[: self.bsz // 2]
                    w, loss = self._sgd(w, self.train_x, self.train_y, jnp.asarray(idx, jnp.int32))
                    client_losses.append(loss)
            losses.append(jnp.mean(jnp.stack(client_losses)))
            share = jnp.float32(cnt / counts.sum())
            total = self._scale(w, share) if total is None else self._add_scaled(total, w, share)
        if self.fault != "state_unchanged":
            self.w = total
        self.round_idx += 1
        return float(jnp.mean(jnp.stack(losses)))

    def evaluate(self) -> dict:
        n, bs = self.test_x.shape[0], 256
        loss = correct = 0.0
        for i in range(0, n, bs):
            l, ok = self._eval(self.w, jnp.asarray(self.test_x[i:i + bs]),
                               jnp.asarray(self.data["test_y"][i:i + bs]))
            loss, correct = loss + float(l), correct + float(ok)
        return {"test_loss": loss / n, "test_acc": correct / n}

    def change_norms(self) -> dict[str, float]:
        return change_norms(self.w, self.w0)
