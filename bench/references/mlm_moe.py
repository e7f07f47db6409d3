"""Plain float32 reference for masked-LM training of the paper's MoE encoders.

Written from the SMILE paper (arXiv:2212.05191 §3-4) and the configuration
file alone; it imports nothing of the program.  One training step is:

* embedding, then ``num_layers / 2`` pairs of a dense block and a MoE block
  (every other FFN is MoE), pre-LayerNorm, bidirectional attention with no
  position encoding, GELU (tanh form) FFNs, final LayerNorm, untied LM head,
  cross-entropy over the masked positions;
* the MoE block routes top-1.  ``smile``: a node router ``p`` over the
  ``n`` nodes of the expert grid, then an in-node router ``q`` over the
  ``E / n`` experts of that node; the output is ``p_i q_j FFN_ij(x)``.
  ``switch``: one router over all ``E`` experts, output ``p_e FFN_e(x)``;
* capacity as the paper defines it, ``C = ceil(cf * tokens / groups)`` over
  the rows a router sees, the padded arrival buffer included for SMILE's
  second level; overflow is dropped in arrival order.  Arrival order follows
  the chips of the cell: a chip's tokens are a contiguous block of batch
  rows (data axis major, model axis minor), level one keeps per chip and
  node, level two keeps per node and model column with the data-axis
  sources in rank order;
* the load-balancing loss ``coef * groups * sum_i f_i P_i`` per level;
* gradient clipping by global norm, then LAMB with bias correction, weight
  decay on leaves stored with two or more dims, trust ratio clipped to
  ``[0, max_trust]``; cosine schedule with linear warm-up.

Every matmul runs at ``Precision.HIGHEST``.  ``precision="control"`` computes
the same step one precision below what the configuration states, which is
how the control of the correctness check is made: every forward matmul
operand and the residual stream rounded to float8 (for the stated
bfloat16), and the parameters and LAMB moments stored in bfloat16 between
steps (for the stated float32).  The gradient is summed over blocks of one chip's
rows, with the routing decided first for the whole batch, so that the
largest model fits one chip.
"""
from __future__ import annotations

import math
import zlib
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
LN_EPS = 1e-5
FP8_MAX = 448.0                       # largest finite float8_e4m3fn


def fp8(x):
    """Round to float8 e4m3 with one scale per tensor (its max magnitude) in
    the forward pass; the gradient passes straight through in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


# precision -> (rounding of matmul operands, dtype the state is kept in)
PRECISIONS = {"fp32": (None, None), "control": (fp8, jnp.bfloat16)}


# =============================================================================
# Parameters
# =============================================================================

def dims(cfg: dict) -> dict:
    m, moe = cfg["model"], cfg["moe"]
    if m["num_layers"] % 2 or moe["every_n_layers"] != 2:
        raise ValueError("the reference models dense/MoE pairs only")
    n, mm = moe["grid"]
    E = moe["num_experts"]
    if E % (n * mm):
        raise ValueError(f"{E} experts do not fill the ({n}, {mm}) grid")
    return dict(d=m["d_model"], H=m["num_heads"], hd=m["d_model"] // m["num_heads"],
                f=m["d_ff"], V=m["vocab_size"], R=m["num_layers"] // 2,
                E=E, n=n, m=mm, e_pn=E // n, fe=moe["d_ff_expert"],
                router=moe["router"], cf=moe["capacity_factor"])


def param_shapes(cfg: dict) -> dict:
    """Shapes of every leaf, in the program's parameter layout (one stage
    of stacked dense/MoE pairs)."""
    k = dims(cfg)
    d, H, hd, R = k["d"], k["H"], k["hd"], k["R"]
    ln = {"scale": (R, d), "bias": (R, d)}
    attn = {"wq": (R, d, H, hd), "wk": (R, d, H, hd), "wv": (R, d, H, hd),
            "wo": (R, H, hd, d)}
    moe = {"experts": {"w1": (R, k["n"], k["e_pn"], d, k["fe"]),
                       "w2": (R, k["n"], k["e_pn"], k["fe"], d)}}
    if k["router"] == "smile":
        moe["router_inter"] = {"w": (R, d, k["n"])}
        moe["router_intra"] = {"w": (R, d, k["e_pn"])}
    else:
        moe["router"] = {"w": (R, d, k["E"])}
    return {
        "embed": {"table": (k["V"], d)},
        "lm_head": {"w": (k["V"], d)},
        "final_norm": {"scale": (d,), "bias": (d,)},
        "stages": ({
            "dense": {"attn": attn, "ffn": {"w1": (R, d, k["f"]),
                                            "w2": (R, k["f"], d)},
                      "ln1": ln, "ln2": ln},
            "moe": {"attn": attn, "ln1": ln, "ln2": ln, "moe": moe},
        },),
    }


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(v, int) for v in x)


def _leaf(key, path: tuple, shape: tuple):
    """One leaf of the initial parameters: norms at identity, embedding and
    head N(0, 0.02), every other matrix N(0, 1/fan_in)."""
    name = path[-1]
    if name == "scale":
        return jnp.ones(shape, jnp.float32)
    if name == "bias":
        return jnp.zeros(shape, jnp.float32)
    if path[0] in ("embed", "lm_head"):
        std = 0.02
    elif name == "wo":
        std = 1.0 / math.sqrt(shape[-3] * shape[-2])
    elif name in ("wq", "wk", "wv"):
        std = 1.0 / math.sqrt(shape[-3])
    else:
        std = 1.0 / math.sqrt(shape[-2])
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF)
    return jax.random.normal(k, shape, jnp.float32) * std


def init_params(cfg: dict, key) -> dict:
    """Initial parameters from ``key``: traceable, so one jitted call makes
    them on the device (sharded, where the caller asks for it)."""
    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        if _is_shape(node):
            return _leaf(key, path, node)
        return tuple(walk(v, path + (str(i),)) for i, v in enumerate(node))
    return walk(param_shapes(cfg), ())


def seed_key(seed: int):
    """A threefry key from any whole number (two 32-bit words)."""
    words = np.random.SeedSequence(seed % 2**64).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32), impl="threefry2x32")


# =============================================================================
# Forward pieces
# =============================================================================

def _ln(p, x):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return jax.nn.gelu(x, approximate=True)


class _Q:
    """Rounding applied at matmul operands and the residual stream."""

    def __init__(self, quant: Optional[Callable]):
        self.q = quant or (lambda x: x)

    def mm(self, eq, a, b):
        return jnp.einsum(eq, self.q(a), self.q(b), precision=HI)


def _attention(p, x, Q: _Q):
    """Bidirectional multi-head attention, x: (b, s, d)."""
    q = Q.mm("bsd,dhk->bshk", x, p["wq"])
    k = Q.mm("bsd,dhk->bshk", x, p["wk"])
    v = Q.mm("bsd,dhk->bshk", x, p["wv"])
    s = Q.mm("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    o = Q.mm("bhqt,bthk->bqhk", jax.nn.softmax(s, axis=-1), v)
    return Q.mm("bqhk,hkd->bqd", o, p["wo"])


def _take(tree, r):
    return jax.tree.map(lambda a: a[r], tree)


def _routers(p, h, k, Q: _Q):
    """Router probabilities for flat tokens h: (t, d)."""
    if k["router"] == "smile":
        return (jax.nn.softmax(Q.mm("td,dn->tn", h, p["router_inter"]["w"]), -1),
                jax.nn.softmax(Q.mm("td,de->te", h, p["router_intra"]["w"]), -1))
    return (jax.nn.softmax(Q.mm("td,de->te", h, p["router"]["w"]), -1),)


def _experts(h, table, scale, w1, w2, Q: _Q, block: int):
    """Expert FFN over the kept tokens.  ``table`` (E, C) holds, per expert,
    the flat token indices it serves (-1 empty); ``scale`` (t,) is each
    token's gate (0 where dropped).  Experts run in checkpointed blocks."""
    E, C = table.shape
    d = h.shape[-1]
    nb = E // block

    def one(args):
        tab, a, b = args
        xs = jnp.take(h, jnp.maximum(tab, 0), axis=0) * (tab >= 0)[..., None]
        return Q.mm("ecf,efd->ecd", _gelu(Q.mm("ecd,edf->ecf", xs, a)), b)

    out = lax.map(jax.checkpoint(one),
                  (table.reshape(nb, block, C),
                   w1.reshape((nb, block) + w1.shape[1:]),
                   w2.reshape((nb, block) + w2.shape[1:])))
    flat_tab = table.reshape(-1)
    gate = jnp.take(scale, jnp.maximum(flat_tab, 0)) * (flat_tab >= 0)
    rows = out.reshape(E * C, d) * gate[:, None]
    idx = jnp.where(flat_tab >= 0, flat_tab, h.shape[0])
    return jnp.zeros_like(h).at[idx].add(rows, mode="drop")


def _ce_sum(h, labels, w, Q: _Q, block: int):
    """Sum of cross-entropy over masked positions; h (t, d), labels (t,)."""
    t, d = h.shape
    nb = t // block

    def one(args):
        hb, lb = args
        logits = Q.mm("td,vd->tv", hb, w)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[:, None],
                                     axis=-1)[:, 0]
        return jnp.sum((lse - picked) * (lb >= 0))

    return jnp.sum(lax.map(jax.checkpoint(one),
                           (h.reshape(nb, block, d), labels.reshape(nb, block))))


def _forward(params, tokens, k, Q: _Q, moe_in: list, upto: int):
    """Run the layers of a chunk of rows.  ``moe_in[r]`` holds MoE layer r's
    fixed decisions; at layer ``upto`` the forward stops and returns that
    layer's router inputs.  Returns (x, lb terms per layer)."""
    st = params["stages"][0]
    x = Q.q(jnp.take(params["embed"]["table"], tokens, axis=0))
    b, s, d = x.shape
    lb = []
    for r in range(k["R"]):
        pd, pm = _take(st["dense"], r), _take(st["moe"], r)
        x = Q.q(x + _attention(pd["attn"], _ln(pd["ln1"], x), Q))
        hf = _ln(pd["ln2"], x)
        x = Q.q(x + Q.mm("bsf,fd->bsd", _gelu(Q.mm("bsd,df->bsf", hf, pd["ffn"]["w1"])),
                         pd["ffn"]["w2"]))
        x = Q.q(x + _attention(pm["attn"], _ln(pm["ln1"], x), Q))
        h = _ln(pm["ln2"], x).reshape(b * s, d)
        probs = _routers(pm["moe"], h, k, Q)
        if r == upto:
            return probs, lb
        dec = moe_in[r]
        if k["router"] == "smile":
            p, q = probs
            gate = (jnp.take_along_axis(p, dec["node"][:, None], -1)[:, 0]
                    * jnp.take_along_axis(q, dec["e"][:, None], -1)[:, 0])
            lb.append(dec["coef"][0] * jnp.sum(dec["f"][0] * p.sum(0))
                      + dec["coef"][1] * jnp.sum(
                          dec["f"][1] * (q * dec["valid2"][:, None]).sum(0)))
        else:
            (p,) = probs
            gate = jnp.take_along_axis(p, dec["e"][:, None], -1)[:, 0]
            lb.append(dec["coef"][0] * jnp.sum(dec["f"][0] * p.sum(0)))
        ex = pm["moe"]["experts"]
        E = k["E"]
        y = _experts(h, dec["table"], gate * dec["keep"],
                     ex["w1"].reshape((E,) + ex["w1"].shape[2:]),
                     ex["w2"].reshape((E,) + ex["w2"].shape[2:]), Q,
                     block=math.gcd(E, 8))
        x = Q.q(x + y.reshape(b, s, d))
    return x, lb


# =============================================================================
# Routing decisions for the whole batch (host side, integers)
# =============================================================================

def _ranks(keys: np.ndarray) -> np.ndarray:
    """Position of each element among the earlier elements with its key."""
    order = np.argsort(keys, kind="stable")
    sk = keys[order]
    first = np.r_[0, np.flatnonzero(sk[1:] != sk[:-1]) + 1]
    run = np.repeat(first, np.diff(np.r_[first, len(sk)]))
    out = np.empty_like(keys)
    out[order] = np.arange(len(sk)) - run
    return out


def decide(k: dict, probs: list, mesh: tuple, rows_per_chip: int, seq: int,
           coef: tuple) -> list:
    """Capacity decisions of one MoE layer for the whole batch.

    ``probs``: per chip, the router probabilities of its tokens (numpy).
    Returns per chip the fixed inputs of that layer's forward."""
    nd, nm = mesh
    t = rows_per_chip * seq
    chips = nd * nm
    cf = k["cf"]
    E = k["E"]
    chip = np.repeat(np.arange(chips), t)
    pos = np.tile(np.arange(t), chips)
    if k["router"] == "smile":
        p = np.concatenate([a[0] for a in probs])
        q = np.concatenate([a[1] for a in probs])
        node = p.argmax(-1)
        e = q.argmax(-1)
        cap1 = math.ceil(t * cf / k["n"])
        keep1 = _ranks(chip * k["n"] + node) < cap1
        cap2 = math.ceil(nd * cap1 * cf / k["e_pn"])
        col = chip % nm
        # level two: per model column and node; data-axis sources in rank
        # order, each in its own token order, which is the global order
        key2 = np.where(keep1, (col * k["n"] + node) * k["e_pn"] + e, -1)
        keep = keep1 & (_ranks(key2) < cap2)
        g = node * k["e_pn"] + e
        f1 = np.bincount(node, minlength=k["n"]) / len(node)
        f2 = (np.bincount(e[keep1], minlength=k["e_pn"])
              / max(int(keep1.sum()), 1))
        cap = cap2
        consts = dict(f=(f1, f2), coef=(coef[0] * k["n"] / len(node),
                                        coef[1] * k["e_pn"] / max(int(keep1.sum()), 1)))
        extra = dict(node=node, valid2=keep1.astype(np.float32))
    else:
        p = np.concatenate([a[0] for a in probs])
        e = p.argmax(-1)
        cap = math.ceil(t * cf / E)
        keep = _ranks(chip * E + e) < cap
        g = e
        f = np.bincount(e, minlength=E) / len(e)
        consts = dict(f=(f,), coef=(coef[0] * E / len(e),))
        extra = {}
    C = min(cap, t)
    out = []
    for c in range(chips):
        sl = slice(c * t, (c + 1) * t)
        gc, kc = g[sl], keep[sl]
        table = np.full((E, C), -1, np.int32)
        idx = np.flatnonzero(kc)
        table[gc[idx], _ranks(gc[idx])] = idx
        dec = {"e": e[sl].astype(np.int32), "keep": kc.astype(np.float32),
               "table": table,
               "f": tuple(np.asarray(v, np.float32) for v in consts["f"]),
               "coef": tuple(np.float32(v) for v in consts["coef"])}
        for name, v in extra.items():
            dec[name] = v[sl].astype(v.dtype if v.dtype != np.int64 else np.int32)
        out.append(dec)
    return out


# =============================================================================
# Training steps
# =============================================================================

class Reference:
    """Three (or more) training steps of one configuration on one device.

    ``mesh`` (data, model) is the layout of the chips the program runs on;
    it decides the capacity pools.  Each chip's rows are one block of the
    gradient."""

    def __init__(self, cfg: dict, mesh: tuple, batch: int, seq: int,
                 precision: str = "fp32", device=None):
        self.cfg, self.k = cfg, dims(cfg)
        self.mesh = tuple(mesh)
        chips = self.mesh[0] * self.mesh[1]
        if batch % chips:
            raise ValueError(f"batch {batch} does not split over {chips} chips")
        self.rows = batch // chips
        self.seq = seq
        rounding, self.state_dtype = PRECISIONS[precision]
        self.Q = _Q(rounding)
        self.device = device or jax.devices()[0]
        tr = cfg["train"]
        self.tr = tr
        moe = cfg["moe"]
        self.coef = ((moe["lb_alpha"], moe["lb_beta"]) if self.k["router"] == "smile"
                     else (moe["lb_alpha"],))
        t = self.rows * seq
        self.ce_block = math.gcd(t, 2048)
        self._route = jax.jit(self._route_fn, static_argnums=(3,))
        self._grad = jax.jit(self._grad_fn)
        self._acc = jax.jit(lambda a, g: jax.tree.map(jnp.add, a, g),
                            donate_argnums=(0,))
        self._round_state = jax.jit(lambda t: jax.tree.map(
            lambda x: x.astype(self.state_dtype).astype(jnp.float32), t),
            donate_argnums=(0,))

    # -- jitted pieces -------------------------------------------------------
    def _route_fn(self, params, tokens, moe_in, upto):
        return _forward(params, tokens, self.k, self.Q, moe_in, upto)[0]

    def _grad_fn(self, params, tokens, labels, moe_in, count):
        def loss(p):
            x, lb = _forward(p, tokens, self.k, self.Q, moe_in, -1)
            b, s, d = x.shape
            h = _ln(p["final_norm"], x.reshape(b * s, d))
            ce = _ce_sum(h, labels.reshape(-1), p["lm_head"]["w"], self.Q,
                         self.ce_block) / count
            return ce + sum(lb, jnp.float32(0.0))
        return jax.value_and_grad(loss)(params)

    # -- one step ------------------------------------------------------------
    def grads(self, params, batch):
        """(loss, gradient) of one batch, summed over the chips' rows."""
        tok, lab = batch["tokens"], batch["labels"]
        chips = self.mesh[0] * self.mesh[1]
        blocks = [(jax.device_put(tok[c * self.rows:(c + 1) * self.rows], self.device),
                   jax.device_put(lab[c * self.rows:(c + 1) * self.rows], self.device))
                  for c in range(chips)]
        count = jnp.float32(max(int((lab >= 0).sum()), 1))
        moe_in = []
        for r in range(self.k["R"]):
            probs = [jax.device_get(self._route(params, tb, moe_in, r))
                     for tb, _ in blocks]
            decs = decide(self.k, probs, self.mesh, self.rows, self.seq,
                          self.coef)
            moe_in.append(decs)
        loss, acc = jnp.float32(0.0), None
        for c, (tb, lb) in enumerate(blocks):
            l, g = self._grad(params, tb, lb, [m[c] for m in moe_in], count)
            loss = loss + l
            acc = g if acc is None else self._acc(acc, g)
            del g
        return float(loss), acc

    def run(self, batches: list, key, keep_grads: bool = False) -> dict:
        """Train len(batches) steps from the initial parameters of ``key``.

        Returns the loss of each step, the norm of each leaf of the first
        clipped gradient, and the norm of each leaf's change over the steps;
        leaves keyed by their path."""
        tr = self.tr
        one = jax.sharding.SingleDeviceSharding(self.device)
        params = jax.jit(lambda kk: init_params(self.cfg, kk),
                         out_shardings=one)(key)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        if self.state_dtype is not None:
            params, m, v = self._round_state((params, m, v))
        update = jax.jit(self._update, donate_argnums=(0, 1, 2))
        losses, first = [], None
        for i, b in enumerate(batches):
            loss, g = self.grads(params, b)
            losses.append(loss)
            params, m, v, gn = update(params, m, v, g, jnp.float32(i + 1))
            if self.state_dtype is not None:
                params, m, v = self._round_state((params, m, v))
            if first is None:
                first = _leaf_norms(gn)
                if keep_grads:
                    grads = {k: np.asarray(a, np.float32) / (1 - tr["b1"])
                             for k, a in _flat_arrays(jax.device_get(m)).items()}
            del g
        del m, v
        change = jax.jit(lambda p, kk: jax.tree.map(
            lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))), p,
            init_params(self.cfg, kk)))(params, key)
        out = {"losses": losses, "grad_norms": first,
               "change_norms": _flat(jax.device_get(change))}
        if keep_grads:
            out["grads"] = grads
        return out

    def _update(self, params, m, v, g, step):
        tr = self.tr
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(g)))
        g = jax.tree.map(lambda x: x * jnp.minimum(
            1.0, tr["grad_clip"] / jnp.maximum(gnorm, 1e-12)), g)
        lr = lr_at(tr, step)
        b1, b2 = tr["b1"], tr["b2"]

        def leaf(p, mm, vv, gg):
            mm = b1 * mm + (1 - b1) * gg
            vv = b2 * vv + (1 - b2) * gg * gg
            dd = (mm / (1 - b1 ** step)) / (jnp.sqrt(vv / (1 - b2 ** step)) + tr["eps"])
            if p.ndim >= 2:
                dd = dd + tr["weight_decay"] * p
            wn, dn = jnp.sqrt(jnp.sum(p * p)), jnp.sqrt(jnp.sum(dd * dd))
            trust = jnp.where((wn > 0) & (dn > 0),
                              jnp.clip(wn / jnp.maximum(dn, 1e-12), 0.0,
                                       tr["max_trust"]), 1.0)
            return p - lr * trust * dd, mm, vv

        leaves, tdef = jax.tree.flatten(params)
        out = [leaf(*a) for a in zip(leaves, jax.tree.leaves(m),
                                     jax.tree.leaves(v), jax.tree.leaves(g))]
        norms = jax.tree.map(lambda x: jnp.sqrt(jnp.sum(x * x)), g)
        return tuple(tdef.unflatten([o[i] for o in out]) for i in range(3)) + (norms,)


def lr_at(tr: dict, step):
    """Cosine schedule with linear warm-up, as a function of the 1-based step."""
    step = jnp.asarray(step, jnp.float32)
    w = float(tr["warmup_steps"])
    warm = tr["lr"] * jnp.minimum(step / max(w, 1.0), 1.0)
    frac = jnp.clip((step - w) / max(tr["total_steps"] - w, 1.0), 0.0, 1.0)
    return jnp.where(step < w, warm, tr["lr"] * 0.5 * (1 + jnp.cos(jnp.pi * frac)))


def _flat_arrays(tree) -> dict:
    out = {}
    for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)] = v
    return out


def _flat(tree) -> dict:
    return {k: float(v) for k, v in _flat_arrays(tree).items()}


def _leaf_norms(norm_tree) -> dict:
    return _flat(jax.device_get(norm_tree))
