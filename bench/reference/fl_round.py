"""Plain reference of the federated round of the MLP configurations
(model kind ``mlp``, ``bench/models/mlp.py``): the same semantics as the
engine's round step with the LT-FL paper's MLP as the client model,
written again in straightforward ``jax.numpy`` with no kernels and no
import of the program.

What a round does (one scenario; ``lax.scan`` strings rounds together,
``vmap`` runs a grid's scenarios side by side):

1. One uniform block per round from ``fold_in(PRNGKey(seed), t)``,
   split in a fixed layout: selection, batch indices, uplink channel
   draws (transition then emission for Gilbert-Elliott), recovery draws
   (ARQ per packet, FEC parity per group). The PRNG stream is part of
   the semantics, like the data: the same draws give the same cohort,
   batches and losses.
2. Selection: uniform without replacement among eligible clients, by
   the largest Gumbel keys.
3. Local training: ``local_steps`` steps of SGD on the client's own
   minibatches of the 60-128-10 MLP (ReLU, softmax cross entropy).
   q-FedAvg clients upload ``L (w_t - w_new)``; FedAvg clients upload
   ``w_new``. The upload is the parameter vector flattened in the order
   the configuration states.
4. Uplink: packets of ``packet_floats`` coordinates; error feedback
   re-injects what a client lost last time; loss by an i.i.d. or
   Gilbert-Elliott channel; sufficient clients (upload speed >= the
   threshold) retransmit and lose nothing; FEC / ARQ recovery; the
   deadline model; faults (NaN device failures, Gaussian corruption of
   delivered packets); screening, norm clipping and the coordinate-wise
   trimmed mean; the group_rate debias.
5. Server: q-FedAvg's normalised step, or FedAvg's (asynchronously
   buffered) mean of models.

Every matrix product goes through one function whose operand precision
is a parameter: float32 at the highest precision for the reference, or
operands rounded to a lower type (the control of PERF.md). Faults for
the checks of the comparison can be planted here too.
"""
from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
RATE_EPS = 1e-6
DENOM_EPS = 1e-12
EMPTY_DUE = 3.0e9
INFEASIBLE_SECS = 1.0e30
MAX_LATENESS = 1.0e6
TRIM_BIG = 3.0e38
CH_INIT_FOLD = 0x4E455453
FAULT_FOLD = 0x46415554
FAULTS = ("half_cohort", "flip_upload")


# -- model -------------------------------------------------------------------

def param_shapes(model: dict) -> dict:
    d_in, d_h, n_c = model["d_in"], model["d_hidden"], model["n_classes"]
    return {"w1": (d_in, d_h), "b1": (d_h,), "w2": (d_h, n_c),
            "b2": (n_c,)}


@functools.partial(jax.jit, static_argnums=(1,))
def _init(keys, dims):
    d_in, d_h, n_c = dims

    def one(key):
        k1, k2 = jax.random.split(key)
        return {"w1": math.sqrt(2.0 / d_in)
                * jax.random.normal(k1, (d_in, d_h)),
                "b1": jnp.zeros((d_h,), jnp.float32),
                "w2": math.sqrt(2.0 / d_h)
                * jax.random.normal(k2, (d_h, n_c)),
                "b2": jnp.zeros((n_c,), jnp.float32)}

    return jax.vmap(one)(keys)


def init_params(keys, model: dict) -> dict:
    """He-normal weights and zero biases, one set per key of ``keys``
    (S, 2), stacked: made on the device in one jitted call."""
    return _init(keys, (model["d_in"], model["d_hidden"],
                        model["n_classes"]))


def flatten(params: dict, order) -> jnp.ndarray:
    return jnp.concatenate([params[k].reshape(-1) for k in order])


def unflatten(vec, model: dict) -> dict:
    shapes = param_shapes(model)
    out, off = {}, 0
    for k in model["param_order"]:
        n = int(np.prod(shapes[k]))
        out[k] = vec[..., off:off + n].reshape(vec.shape[:-1] + shapes[k])
        off += n
    return out


def make_matmul(dot_dtype):
    """``a @ b`` in float32 at the highest precision, or with every
    operand of the forward and backward products rounded to
    ``dot_dtype`` first (float32 accumulation)."""
    if dot_dtype is None:
        return lambda a, b: jnp.matmul(a, b, precision=HIGHEST)

    def r(x):
        return x.astype(dot_dtype).astype(jnp.float32)

    @jax.custom_vjp
    def mm(a, b):
        return jnp.matmul(r(a), r(b), precision=HIGHEST)

    def fwd(a, b):
        return mm(a, b), (a, b)

    def bwd(res, g):
        a, b = res
        ga = jnp.matmul(r(g), r(b).T, precision=HIGHEST)
        gb = jnp.matmul(r(a).T, r(g), precision=HIGHEST)
        return ga, gb

    mm.defvjp(fwd, bwd)
    return mm


def make_loss(mm):
    def loss(p, x, y):
        h = jax.nn.relu(mm(x, p["w1"]) + p["b1"])
        logits = mm(h, p["w2"]) + p["b2"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
        return (lse - ll).mean()
    return loss


# -- uplink pieces -----------------------------------------------------------

def ge_probs(loss_rate, burst_len, good, bad):
    pi_b = jnp.clip((loss_rate - good) / max(bad - good, RATE_EPS),
                    0.0, 1.0 - RATE_EPS)
    p_bg = 1.0 / max(burst_len, 1.0)
    p_gb = jnp.clip(p_bg * pi_b / jnp.maximum(1.0 - pi_b, RATE_EPS),
                    0.0, 1.0)
    return pi_b, p_gb, p_bg


def ge_mask(u_t, u_e, s0, p_gb, p_bg, good, bad):
    """Per packet: change state with its transition probability, then
    lose the packet with the new state's loss probability."""
    mask = []
    s = s0
    for p in range(u_t.shape[1]):
        flip = jnp.where(s == 1, p_bg, p_gb)
        s = jnp.where(u_t[:, p] < flip, 1 - s, s)
        h = jnp.where(s == 1, bad, good)
        mask.append((u_e[:, p] >= h).astype(jnp.float32))
    return jnp.stack(mask, axis=1), s


def fec_repair(mask, parity, group):
    """A group with exactly one lost packet and its parity delivered is
    repaired."""
    C, P = mask.shape
    out = mask
    for g in range(parity.shape[1]):
        lo, hi = g * group, min((g + 1) * group, P)
        lost = (mask[:, lo:hi] < 0.5).sum(axis=1)
        fix = (lost == 1) & (parity[:, g] > 0.5)
        out = out.at[:, lo:hi].set(
            jnp.where(fix[:, None], 1.0, mask[:, lo:hi]))
    return out


def trimmed_mean(y, valid, k):
    """Per coordinate: drop the k largest and k smallest valid values
    and average the rest; with 2k or fewer valid values, the plain
    masked mean. y (C, D), valid (C, D) 0/1."""
    C = y.shape[0]
    vb = valid > 0.0
    n = valid.sum(0)
    total = (y * valid).sum(0)
    lo = jnp.sort(jnp.where(vb, y, TRIM_BIG), axis=0)[:k].sum(0)
    hi = jnp.sort(jnp.where(vb, y, -TRIM_BIG), axis=0)[C - k:].sum(0)
    return jnp.where(n > 2.0 * k, (total - hi - lo)
                     / jnp.maximum(n - 2.0 * k, 1.0),
                     total / jnp.maximum(n, 1.0))



def aggregate(x, mask, w_up, scale, F, *, screen, clip_norm, trim_k):
    """The server's combine of C uploads x (C, D) under the delivery
    mask (C, P): screening (a packet with a non-finite float is dropped
    as if lost), norm clipping, the debiased weighted mean
    sum_c w_c s_c x_c / sum_c w_c with the per-client ``scale`` s_c, or
    the trimmed mean of the scaled uploads. Returns the aggregate, the
    screened uploads, the per-packet finite bits, the clip factors and
    the masked squared norms."""
    C, D = x.shape
    P = mask.shape[1]
    pad = P * F - D
    if screen:
        fin = jnp.isfinite(x)
        x = jnp.where(fin, x, 0.0)
        pk_ok = jnp.pad(fin, ((0, 0), (0, pad)),
                        constant_values=True).reshape(C, P, F).all(-1)
        m_eff = mask * pk_ok.astype(jnp.float32)
    else:
        pk_ok = jnp.ones((C, P), bool)
        m_eff = mask
    c_eff = jnp.repeat(m_eff, F, axis=1)[:, :D]
    ssq = (x * x * c_eff).sum(1)
    s_clip = jnp.ones((C,), jnp.float32)
    if clip_norm is not None:
        s_clip = jnp.where(ssq > clip_norm * clip_norm,
                           clip_norm / jnp.sqrt(jnp.maximum(ssq, DENOM_EPS)),
                           1.0)
    q = w_up * scale * s_clip
    agg = (x * c_eff * q[:, None]).sum(0) \
        / jnp.maximum(w_up.sum(), DENOM_EPS)
    if trim_k:
        valid = c_eff * (w_up > 0.0).astype(jnp.float32)[:, None]
        agg = trimmed_mean(x * (scale * s_clip)[:, None], valid, trim_k)
    return agg, x, pk_ok, s_clip, ssq


# -- the round ---------------------------------------------------------------

class Reference:
    """The configuration's rounds for S scenarios over shared data.

    ``fl`` is the configuration's ``fl`` block, ``model`` its ``model``
    block; ``scenarios`` a list of dicts with ``seed``, ``loss_rate``
    and ``recovery_policy``; ``data`` = (x (N, M, 60), y (N, M),
    counts (N,)); ``upload_mbps`` (N,). ``dot_dtype`` rounds matrix
    product operands (None: float32 at the highest precision);
    ``fault`` plants one of ``FAULTS``.
    """

    POLICIES = ("one_shot", "fec", "arq")

    def __init__(self, fl: dict, model: dict, scenarios, data,
                 upload_mbps, *, dot_dtype=None, fault=None):
        _Rounds.check(fl)
        if fault is not None and fault not in FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.fl, self.model = fl, model
        self.order = model["param_order"]
        self.D = sum(int(np.prod(s)) for s in param_shapes(model).values())
        x, y, counts = data
        mbps = np.asarray(upload_mbps, np.float32)
        self.data = {
            "x": jnp.asarray(x, jnp.float32),
            "y": jnp.asarray(y, jnp.int32),
            "counts": jnp.asarray(counts, jnp.int32),
            "suff": jnp.asarray((mbps >= fl["tra"]["threshold_mbps"])
                                .astype(np.float32)),
            "logbw": jnp.log(jnp.asarray(mbps, jnp.float32))}
        self.N = int(np.asarray(counts).shape[0])
        self.scen = {
            "key": jnp.stack([jax.random.PRNGKey(s["seed"])
                              for s in scenarios]),
            "loss_rate": jnp.asarray([s["loss_rate"] for s in scenarios],
                                     jnp.float32),
            "rec_oh": jnp.asarray(
                [[float(p == s["recovery_policy"]) for p in self.POLICIES]
                 for s in scenarios], jnp.float32)}
        self._block = _block_program(json.dumps(fl, sort_keys=True),
                                     json.dumps(model, sort_keys=True),
                                     None if dot_dtype is None
                                     else jnp.dtype(dot_dtype).name, fault)

    # -- state ------------------------------------------------------------
    def init_states(self, params_list):
        """Stacked state of every scenario from its initial weights."""
        fl, N, D = self.fl, self.N, self.D
        ns = fl.get("netsim", {})
        K = fl.get("srv", {}).get("buffer_k", 0)
        states = []
        for i, p in enumerate(params_list):
            st = {"w": flatten(p, self.order).astype(jnp.float32),
                  "ef": jnp.zeros((N, D), jnp.float32),
                  "echo": jnp.zeros((N, D), jnp.float32)}
            if ns.get("channel") == "gilbert_elliott":
                pi_b, _, _ = ge_probs(self.scen["loss_rate"][i],
                                      ns["burst_len"], ns["good_loss"],
                                      ns["bad_loss"])
                u = jax.random.uniform(
                    jax.random.fold_in(self.scen["key"][i], CH_INIT_FOLD),
                    (N,))
                st["chan"] = (u < pi_b).astype(jnp.int32)
            else:
                st["chan"] = jnp.zeros((N,), jnp.int32)
            st["buf"] = {"vec": jnp.zeros((K, D), jnp.float32),
                         "due": jnp.full((K,), EMPTY_DUE, jnp.float32),
                         "w": jnp.zeros((K,), jnp.float32),
                         "tau": jnp.zeros((K,), jnp.float32)}
            states.append(st)
        return jax.tree.map(lambda *xs: jnp.stack(xs), *states)

    def run(self, states, t0: int, k: int):
        """Rounds [t0, t0 + k) of every scenario. Returns the new states
        and the logged train losses (S, k)."""
        ts = jnp.arange(t0, t0 + k, dtype=jnp.int32)
        states, losses = self._block(self.data, self.scen, states, ts)
        return states, losses.T

    def params(self, states) -> dict:
        return unflatten(states["w"], self.model)

    def ef(self, states):
        return states["ef"]


@functools.lru_cache(maxsize=None)
def _block_program(fl_json: str, model_json: str, dot_dtype, fault):
    """One jitted program of k rounds of S scenarios per configuration,
    precision and fault, shared by every ``Reference`` that needs it."""
    rounds = _Rounds(json.loads(fl_json), json.loads(model_json),
                     dot_dtype, fault)
    body = jax.vmap(rounds.round, in_axes=(None, 0, 0, None))
    return jax.jit(lambda data, sc, st, ts: jax.lax.scan(
        lambda s, t: body(data, sc, s, t), st, ts))


class _Rounds:
    """The round of one configuration, precision and fault: static
    structure only, the data and scenarios come in as arguments."""

    def __init__(self, fl, model, dot_dtype, fault):
        self.fl, self.model, self.fault = fl, model, fault
        self.order = model["param_order"]
        self.D = sum(int(np.prod(s)) for s in param_shapes(model).values())
        self.F = fl["tra"]["packet_floats"]
        self.P = -(-self.D // self.F)
        self.loss = make_loss(make_matmul(
            None if dot_dtype is None else jnp.dtype(dot_dtype)))

    @staticmethod
    def check(fl):
        """The features this reference implements; anything else is
        refused rather than silently left out."""
        ns, srv = fl.get("netsim", {}), fl.get("srv", {})
        if fl["algo"] not in ("qfedavg", "fedavg"):
            raise ValueError(f"algo {fl['algo']!r} has no reference")
        if fl.get("selection", "all") != "all" \
                or fl.get("sel", {}).get("policy", "uniform") != "uniform":
            raise ValueError("only uniform selection over all clients")
        if not fl["tra"]["enabled"] or fl["tra"]["debias"] != "group_rate":
            raise ValueError("only TRA with the group_rate debias")
        if ns.get("bw_ar1") or ns.get("down_channel", "off") != "off":
            raise ValueError("no bandwidth walk, no downlink loss")
        if srv.get("mode", "sync") not in ("sync", "async") \
                or srv.get("traced"):
            raise ValueError("sync or async server, not traced")
        if fl.get("faults", {}).get("bitflip_rate", 0.0):
            raise ValueError("no bit-flip faults")
        if fl.get("lossbudget", {}).get("enabled"):
            raise ValueError("no loss-budget controller")

    # -- one round, one scenario ------------------------------------------
    def local(self, w0_vec, X, Y):
        fl = self.fl
        lr = fl["lr"]
        p0 = unflatten(w0_vec, self.model)
        loss0 = self.loss(p0, X.reshape(-1, X.shape[-1]), Y.reshape(-1))

        def step(p, xy):
            x, y = xy
            g = jax.grad(self.loss)(p, x, y)
            return jax.tree.map(lambda a, b: a - lr * b, p, g), None

        p1 = jax.lax.scan(step, p0, (X, Y))[0]
        w1 = flatten(p1, self.order)
        if fl["algo"] == "qfedavg":
            up = fl["lipschitz"] * (w0_vec - w1)
        else:
            up = w1
        return up, loss0

    def round(self, data, sc, st, t):
        fl, D, P, F = self.fl, self.D, self.P, self.F
        N = data["counts"].shape[0]
        C, steps, bs = (fl["clients_per_round"], fl["local_steps"],
                        fl["batch_size"])
        ns = fl.get("netsim", {})
        srv = fl.get("srv", {"mode": "sync"})
        flt = fl.get("faults", {"enabled": False})
        dfn = fl.get("defense", {})
        rec = fl.get("recovery", {"policy": "one_shot", "traced": False})
        use_ge = ns.get("channel") == "gilbert_elliott"
        use_dl = bool(ns.get("deadline"))
        use_rec = rec["traced"] or rec["policy"] != "one_shot"
        use_async = srv["mode"] == "async"
        use_faults = flt["enabled"]
        lr_nom = sc["loss_rate"]

        # 1. the round's uniforms
        key = jax.random.fold_in(sc["key"], t)
        n_batch = C * steps * bs
        n_tra = (2 if use_ge else 1) * C * P
        gn = -(-P // rec.get("group", 8)) if use_rec else 0
        n_rec = C * P + C * gn if use_rec else 0
        u = jax.random.uniform(key, (N + n_batch + n_tra + n_rec,),
                               minval=1e-12, maxval=1.0)
        u_sel, off = u[:N], N
        u_idx = u[off:off + n_batch].reshape(C, steps, bs)
        off += n_batch
        u_tra = u[off:off + C * P].reshape(C, P)
        u_emit = u[off + C * P:off + 2 * C * P].reshape(C, P) \
            if use_ge else None
        off += n_tra
        if use_rec:
            u_arq = u[off:off + C * P].reshape(C, P)
            u_par = u[off + C * P:off + n_rec].reshape(C, gn)

        # 2. selection: the C largest Gumbel keys
        gumbel = -jnp.log(-jnp.log(u_sel))
        ids = jnp.argsort(-gumbel)[:C]

        # 3. local training on each client's own minibatches
        counts = data["counts"][ids]
        idx = jnp.minimum((u_idx * counts[:, None, None]).astype(jnp.int32),
                          counts[:, None, None] - 1)
        X = data["x"][ids[:, None, None], idx]
        Y = data["y"][ids[:, None, None], idx]
        up, loss0 = jax.vmap(self.local, in_axes=(None, 0, 0))(
            st["w"], X, Y)
        if self.fault == "flip_upload":
            up = up.at[0].set(-up[0])
        weights = counts.astype(jnp.float32) / counts.sum()
        suff = data["suff"][ids]
        clean = up

        # 4a. client faults: NaN device failures (echo, sign flip)
        if use_faults:
            fkey = jax.random.fold_in(key, FAULT_FOLD)
            uf = jax.random.uniform(jax.random.fold_in(fkey, 0), (3, C))
            up = jnp.where((uf[0] < flt["echo_rate"])[:, None],
                           st["echo"][ids], up)
            up = jnp.where((uf[1] < flt["flip_rate"])[:, None], -up, up)
            up = jnp.where((uf[2] < flt["fail_rate"])[:, None], jnp.nan, up)

        # 4b. the channel
        chan = st["chan"]
        if use_ge:
            _, p_gb, p_bg = ge_probs(lr_nom, ns["burst_len"],
                                     ns["good_loss"], ns["bad_loss"])
            base, s_fin = ge_mask(u_tra, u_emit, chan[ids], p_gb, p_bg,
                                  ns["good_loss"], ns["bad_loss"])
            chan = chan.at[ids].set(s_fin)
        else:
            base = (u_tra >= lr_nom).astype(jnp.float32)
        r = jnp.clip(lr_nom, 0.0, 1.0)
        if use_rec:
            G, m = rec["group"], max(rec["retries"], 0.0)
            parity = (u_par >= r).astype(jnp.float32)
            fec = fec_repair(base, parity, G)
            arq = jnp.where((base < 0.5) & ~(u_arq < r ** m), 1.0, base)
            oh = sc["rec_oh"]
            mask = oh[0] * base + oh[1] * fec + oh[2] * arq
            r_deb = oh[0] * r + oh[1] * r * (1.0 - (1.0 - r) ** G) \
                + oh[2] * r ** (1.0 + m)
        else:
            mask, r_deb = base, lr_nom
        mask = jnp.where(suff[:, None] > 0.5, 1.0, mask)

        # 4c. the deadline: seconds to push this round's packets
        a_c = jnp.ones((C,), jnp.float32)
        lateness = jnp.zeros((C,), jnp.float32)
        if use_dl:
            sends = 1.0
            if use_rec:
                G, m = rec["group"], max(rec["retries"], 0.0)
                geo = r * (1.0 - r ** m) / jnp.maximum(1.0 - r, RATE_EPS)
                extra = jnp.where(r > 1.0 - RATE_EPS, m, jnp.minimum(geo, m))
                arq_sends = 1.0 + max(rec["backoff"], 0.0) * extra
                sends = oh[0] + oh[1] * (1.0 + 1.0 / G) + oh[2] * arq_sends
            sends = jnp.where(suff > 0.5,
                              1.0 / jnp.maximum(1.0 - r, RATE_EPS), sends)
            mbps = jnp.exp(data["logbw"][ids])
            bits = float(P * F * 4 * 8)
            secs = bits * sends / (jnp.maximum(mbps, RATE_EPS) * 1e6)
            ok = jnp.isfinite(secs) & (secs > 0.0) & (mbps > 0.0)
            secs = jnp.where(ok, secs, INFEASIBLE_SECS)
            dl = ns["deadline_s"]
            ontime = (secs <= dl).astype(jnp.float32) if dl > 0 \
                else jnp.zeros((C,), jnp.float32)
            lateness = jnp.clip(jnp.ceil(secs / dl) - 1.0, 0.0,
                                MAX_LATENESS) if dl > 0 \
                else jnp.full((C,), MAX_LATENESS)
            if use_async:
                a_c = ontime
            else:
                mask = mask * ontime[:, None]

        # 4d. corruption of delivered packets. From here on an upload is
        # its packets: P * F floats, the last packet's tail zero-padded.
        # Corruption hits a whole packet, tail included, and the
        # server's norms run over every float it received.
        pad = P * F - D
        coords = jnp.repeat(mask, F, axis=1)
        x = jnp.pad(up, ((0, 0), (0, pad)))
        if use_faults:
            kg = jax.random.fold_in(fkey, 1)
            uc = jax.random.uniform(jax.random.fold_in(kg, 0), (2, C, P))
            hit = (uc[0] < flt["corrupt_rate"]) & (mask > 0.5)
            noise = flt["corrupt_scale"] * jax.random.normal(
                jax.random.fold_in(kg, 1), (C, P, F), jnp.float32)
            x = jnp.where(jnp.repeat(hit, F, axis=1), x
                          + noise.reshape(C, P * F), x)
        x = x + jnp.pad(st["ef"][ids], ((0, 0), (0, pad)))

        # 4e. aggregation: screen, clip, debias, (trimmed) mean
        w_agg = jnp.ones((C,), jnp.float32) if fl["algo"] == "qfedavg" \
            else weights
        if self.fault == "half_cohort":
            w_agg = w_agg * (jnp.arange(C) < C // 2)
        w_up = w_agg * a_c
        if fl["algo"] == "qfedavg":
            fq = (loss0 + 1e-10) ** fl["q"]
        else:
            fq = jnp.ones((C,), jnp.float32)
        debias = jnp.where(suff > 0.5, 1.0,
                           1.0 / jnp.maximum(1.0 - r_deb, RATE_EPS))
        screen = use_faults and dfn.get("screen", False)
        agg, x, pk_ok, s_clip, ssq = aggregate(
            x, mask, w_up, fq * debias, F, screen=screen,
            clip_norm=dfn["clip_norm"] if use_faults and dfn.get("clip")
            else None,
            trim_k=dfn["trim_k"] if use_faults and dfn.get("trim") else 0)
        agg = agg[:D]
        ef = st["ef"].at[ids].set((x * (1.0 - coords))[:, :D]) \
            if fl["error_feedback"] else st["ef"]

        # 4f. the asynchronous buffer: late uploads land later, discounted
        buf = st["buf"]
        den_ready = jnp.float32(0.0)
        if use_async:
            alpha = srv["staleness_alpha"]
            tf = t.astype(jnp.float32)
            ready = buf["due"] <= tf
            w_tau = (1.0 + jnp.maximum(buf["tau"], 0.0)) ** (-alpha) \
                * ready.astype(jnp.float32)
            num_ready = (w_tau[:, None] * buf["vec"]).sum(0)
            den_ready = (w_tau * buf["w"]).sum()
            keep = (~ready).astype(jnp.float32)
            buf = {"vec": buf["vec"] * keep[:, None],
                   "due": jnp.where(ready, EMPTY_DUE, buf["due"]),
                   "w": buf["w"] * keep, "tau": buf["tau"] * keep}
            den_on = w_up.sum()
            merged = (agg * jnp.maximum(den_on, DENOM_EPS) + num_ready) \
                / jnp.maximum(den_on + den_ready, DENOM_EPS)
            agg = jnp.where(den_ready > 0.0, merged, agg)
            rows = up + st["ef"][ids]
            if screen:
                rows = jnp.where(jnp.isfinite(rows), rows, 0.0)
            contrib = rows * coords[:, :D] * (w_agg * fq * debias
                                       * s_clip)[:, None]
            live = (lateness > 0.0) & (lateness < MAX_LATENESS)
            if use_faults:
                qcnt = (mask * (1.0 - pk_ok.astype(jnp.float32))).sum(1)
                if screen:
                    live = live & ~(qcnt > 0.0)
            lf = live.astype(jnp.float32)
            all_due = jnp.concatenate(
                [buf["due"], jnp.where(live, tf + lateness, EMPTY_DUE)])
            order = jnp.argsort(all_due, stable=True)[:buf["due"].shape[0]]
            buf = {"vec": jnp.concatenate([buf["vec"],
                                           contrib * lf[:, None]])[order],
                   "due": all_due[order],
                   "w": jnp.concatenate([buf["w"], w_agg * lf])[order],
                   "tau": jnp.concatenate([buf["tau"],
                                           lateness * lf])[order]}

        # 5. the server step
        w_old = st["w"]
        if fl["algo"] == "qfedavg":
            qq = fl["q"]
            h = qq * (loss0 + 1e-10) ** (qq - 1.0) * ssq \
                + fl["lipschitz"] * fq
            w_new = w_old - agg * C / jnp.maximum(h.sum(), 1e-8)
        else:
            w_new = agg
        if use_async:
            w_new = jnp.where(w_up.sum() + den_ready > 0.0, w_new, w_old)
        echo = st["echo"].at[ids].set(clean) if use_faults else st["echo"]
        new = {"w": w_new, "ef": ef, "echo": echo, "chan": chan,
               "buf": buf}
        return new, loss0.mean()
