"""The comparisons that decide ``correct``.

Serving: once the window has closed and the engine is gone, a sample of the
requests the timed engine finished (the longest of each kind among them) is
run through the plain reference, teacher-forced over prompt + served tokens.
Compared are, for the requests that are greedy in effect, the widest gap by
which a served token's reference logit lies below the reference's best at
that position; for the sampled ones, the widest gap by which a served token
lies below the lowest logit that the reference's own nucleus keeps (a cut
that is missing, a token from a wrong row or page), and how far the
probability of the tokens above the served one is, on average, from what a
sound sampler's draws give (a wrong temperature or cut level).

Training: the reference follows the program's first two steps on the same
batches (float32 AdamW with global-norm clipping, written plainly) after
the window, once the program's state is freed. Compared: each step's loss, every leaf's
gradient norm as the optimizer got it (the program's from its first moment
after step one, m / (1 - beta1)), every norm gain's gradient as a vector,
and every leaf's norm of change after the two steps.

Every number is printed beside its limit in every run. The limits live in
the cell's file with the readings they were set from (PERF.md section 2).
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import weights as W


@dataclasses.dataclass
class Compared:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit

    def line(self) -> str:
        return (f"compared {self.name}: {self.value:.6g} (limit "
                f"{self.limit:.6g}) {'ok' if self.ok else 'NOT OK'}")


def round_fp8(x):
    """To the nearest float8_e4m3fn value and back: the control's lower
    precision. Written as arithmetic (3 mantissa bits, exponents -6..8,
    subnormals in steps of 2**-9, round half to even, saturating at 448):
    the TPU's compiler folds a convert to fp8 and back into nothing."""
    import jax.numpy as jnp

    a = jnp.abs(x.astype(jnp.float32))
    _, ex = jnp.frexp(a)                       # a = m * 2**ex, m in [0.5, 1)
    step = jnp.ldexp(jnp.float32(1.0), jnp.clip(ex - 1, -6, 8) - 3)
    r = jnp.minimum(jnp.round(a / step) * step, 448.0)
    return (jnp.sign(x) * r).astype(x.dtype)


# ---- serving ----------------------------------------------------------------

def pick_sample(done, seed: int, spec: dict):
    """Of the unfailed requests the window finished, for each kind (greedy
    in effect, sampled): the longest, then others drawn from the seed until
    the kind's ``requests`` and ``min_tokens`` served tokens are reached."""
    picked = []
    for greedy, kind in ((True, "greedy"), (False, "sampled")):
        want = spec[kind]
        pool = [lv for lv in done if lv.plan.greedy == greedy
                and not lv.req.failed and len(lv.req.output) > 0]
        if not pool:
            continue
        pool.sort(key=lambda lv: (-(len(lv.plan.prompt) + len(lv.req.output)),
                                  lv.plan.index))
        mine, rest = pool[:1], pool[1:]
        rng = np.random.Generator(np.random.PCG64([int(seed), 3 + greedy]))
        for i in rng.permutation(len(rest)):
            tokens = sum(len(lv.req.output) for lv in mine)
            if (len(mine) >= int(want["requests"])
                    and tokens >= int(want["min_tokens"])) \
                    or len(mine) >= 3 * int(want["requests"]):
                break
            mine.append(rest[int(i)])
        picked += mine
    return picked


def served_stats(cell, seed: int, sample, sampling: dict, lower=None,
                 draw=None, bucket: int = 512):
    """Per request of the sample, what the reference says of every served
    token, teacher-forced over prompt + served tokens: ``best_gap`` (its
    logit below the reference's best), and for a sampled request
    ``nucleus_gap`` (its logit below the lowest that the reference's own
    nucleus keeps; negative inside) and ``mass_off`` (the probability of
    the tokens above it, less what a sound sampler's draws average).

    The controls put the reference in the program's place: with ``draw`` =
    {"temperature", "top_p"} the tokens judged are not the served ones but,
    at the same positions, the reference's own first token (greedy
    requests) or a token it draws with those settings (sampled requests);
    with ``lower`` as well, a reference whose weights went through
    ``lower`` picks them. Weights are made a layer at a time."""
    import jax

    cfg, ref, table = cell.config, cell.reference, cell.leaf_table
    top = W.top_weights(table, seed)
    layer = lambda i: W.layer_weights(table, seed, i)
    rows = []
    for lv in sample:
        out = np.asarray(lv.req.output, np.int32)
        ids = np.concatenate([lv.plan.prompt, out])
        n, k = len(ids), len(out)
        padded = np.zeros(-(-n // bucket) * bucket, np.int32)
        padded[:n] = ids
        # positions and tokens padded too (the last one repeated)
        width = -(-k // 128) * 128
        pos = np.full(width, n - 2, np.int32)
        pos[:k] = np.arange(len(lv.plan.prompt) - 1, n - 1)
        tok = np.full(width, out[-1], np.int32)
        tok[:k] = out
        rows.append((lv, padded, pos, tok, k))
    xs = ref.hidden_states_many(cfg, [r[1][None] for r in rows], layer, top)
    if draw is not None:
        picker_top, picker_xs = top, xs
        if lower is not None:
            picker_top = {k: lower(v) for k, v in top.items()}
            picker_xs = ref.hidden_states_many(
                cfg, [r[1][None] for r in rows],
                lambda i: {k: lower(v) for k, v in layer(i).items()},
                picker_top)
    t, p = float(sampling["temperature"]), float(sampling["top_p"])
    stats = []
    for j, ((lv, _, pos, tok, k), x) in enumerate(zip(rows, xs)):
        if draw is not None:
            key = jax.random.fold_in(W.seed_key(seed), lv.plan.index)
            first, drawn = ref.draw_tokens(
                cfg, picker_xs[j][0], pos, picker_top,
                float(draw["temperature"]), float(draw["top_p"]), key)
            tok = first if lv.plan.greedy else drawn
        got = ref.token_stats(cfg, x[0], pos, tok, top,
                              *((1.0, 1.0) if lv.plan.greedy else (t, p)))
        got = {name: np.asarray(v, np.float64)[:k] for name, v in got.items()}
        stats.append({"greedy": lv.plan.greedy, "best_gap": got["best_gap"],
                      "nucleus_gap": got["nucleus_gap"],
                      "mass_off": got["mass_above"]
                      - got["mass_above_expected"]})
    return stats


def served_numbers(stats) -> dict:
    """The three numbers compared, and the tokens they were taken over."""
    greedy = [s["best_gap"] for s in stats if s["greedy"]]
    inside = [s["nucleus_gap"] for s in stats if not s["greedy"]]
    off = [s["mass_off"] for s in stats if not s["greedy"]]
    n_sampled = int(sum(len(g) for g in inside))
    return {
        "served_token_logit_gap": max((float(g.max()) for g in greedy),
                                      default=float("inf")),
        "sampled_token_nucleus_gap": max(
            (max(0.0, float(g.max())) for g in inside),
            default=float("inf")),
        "sampled_mass_above_off": abs(float(np.concatenate(off).mean()))
        if n_sampled else float("inf"),
        "greedy_tokens": int(sum(len(g) for g in greedy)),
        "sampled_tokens": n_sampled,
    }


def check_served(cell, seed: int, done, sched):
    """The serving comparisons; returns (list of Compared, facts)."""
    spec, lim = cell.spec["check"], cell.spec["limits"]
    vocab, eos = int(cell.config["vocab_size"]), sched.eos_token_id
    wrong = 0
    for lv in done:
        out = lv.req.output
        if lv.req.failed:
            continue
        ended = len(out) == lv.plan.max_new or (out and out[-1] == eos)
        if not ended or any(t < 0 or t >= vocab for t in out) \
                or len(out) > lv.plan.max_new:
            wrong += 1
    sample = pick_sample(done, seed, spec)
    got = served_numbers(served_stats(cell, seed, sample, sched.sampling)
                         if sample else [])
    out = [Compared(name, got[name], float(lim[name]))
           for name in ("served_token_logit_gap", "sampled_token_nucleus_gap",
                        "sampled_mass_above_off")]
    out.append(Compared("malformed_streams", float(wrong), 0.0))
    for kind in ("greedy", "sampled"):
        out.append(Compared(f"{kind}_tokens_short_of", float(max(
            0, int(spec[kind]["min_tokens"]) - got[f"{kind}_tokens"])), 0.0))
    return out, {"checked_requests": len(sample),
                 "checked_tokens": got["greedy_tokens"]
                 + got["sampled_tokens"]}


# ---- training ---------------------------------------------------------------

def leaf_key(adapter, name: str) -> str:
    layer, leaf = adapter.leaf_of(name)
    return leaf if layer is None else f"{layer}.{leaf}"


def _flat(tree: dict) -> dict:
    out = {k: v for k, v in tree.items() if k != "layers"}
    for i, lw in enumerate(tree["layers"]):
        out.update({f"{i}.{k}": v for k, v in lw.items()})
    return out


def reference_training(cell, seed: int, batches, hyper: dict, lower=None):
    """The reference's first two steps. Returns losses, per-leaf clipped
    gradient norms, the norm gains' clipped gradients, and per-leaf norms of
    the change after two steps. With ``lower`` the weights are put through
    it first (the control)."""
    import jax
    import jax.numpy as jnp

    cfg, ref, table = cell.config, cell.reference, cell.leaf_table
    b1, b2 = float(hyper["beta1"]), float(hyper["beta2"])
    kw = dict(lr=float(hyper["lr"]), b1=b1, b2=b2, eps=float(hyper["epsilon"]),
              wd=float(hyper["weight_decay"]))
    p = W.model_weights(table, seed, dtype=jnp.float32)
    if lower is not None:
        p = jax.jit(lambda t: jax.tree_util.tree_map(lower, t),
                    donate_argnums=0)(p)
    vg = jax.jit(jax.value_and_grad(lambda w, ids: ref.loss_of(cfg, w, ids)))

    def _clip(g):
        g = ref.clip_by_global_norm(g, float(hyper["clip_norm"]))
        return g, jax.tree_util.tree_map(jnp.linalg.norm, g)

    clip = jax.jit(_clip, donate_argnums=0)
    step1 = jax.jit(lambda p, g: ref.adamw_leaf(p, jnp.zeros_like(p),
                                                jnp.zeros_like(p), g, 1.0,
                                                **kw)[0], donate_argnums=0)

    def step2(p, g1, g2, p_start):
        m1 = (1 - b1) * g1
        v1 = (1 - b2) * g1 * g1
        p2 = ref.adamw_leaf(p, m1, v1, g2, 2.0, **kw)[0]
        return jnp.linalg.norm(p2 - p_start)

    step2 = jax.jit(step2, donate_argnums=(0, 1, 2, 3))

    ids1, ids2 = (jnp.asarray(b) for b in batches[:2])
    loss1, g = vg(p, ids1)
    g, gn = clip(g)
    gn = {k: float(v) for k, v in _flat(gn).items()}
    flat_g = _flat(g)
    vec = {k: np.asarray(v) for k, v in flat_g.items() if v.ndim == 1}
    # step 1, leaf by leaf; the clipped gradient goes to the host, since
    # step 2 needs it as the optimizer's state and the chip has no room
    g1_host = {}
    flat_p = _flat(p)
    del p, g
    for k in list(flat_p):
        gk = flat_g.pop(k)
        g1_host[k] = np.asarray(gk)
        flat_p[k] = step1(flat_p[k], gk)
    n_layers = len(table["layers"])
    tree = {k: v for k, v in flat_p.items() if "." not in k}
    tree["layers"] = [{k.split(".", 1)[1]: flat_p[k] for k in flat_p
                       if k.startswith(f"{i}.")} for i in range(n_layers)]
    del flat_p
    loss2, g = vg(tree, ids2)
    g, _ = clip(g)
    flat_g, flat_p = _flat(g), _flat(tree)
    del g, tree
    start = {}
    dn = {}
    for k in list(flat_p):
        if "." in k:
            i, leaf = k.split(".", 1)
            if ("L", i) not in start:
                start.clear()
                start[("L", i)] = W.layer_weights(table, seed, int(i))
            s = start[("L", i)][leaf]
        else:
            s = W.top_weights(table, seed, (k,))[k]
        if lower is not None:
            s = lower(s)
        dn[k] = float(step2(flat_p.pop(k), jnp.asarray(g1_host.pop(k)),
                            flat_g.pop(k), s))
    return {"loss": [float(loss1), float(loss2)], "grad_norm": gn,
            "gain_grad": vec, "change_norm": dn}


def program_norms(adapter, names, arrays, scale: float = 1.0):
    """Per-leaf norms (and the 1-D leaves whole) of a list of the program's
    arrays, keyed like the reference's leaves."""
    import jax
    import jax.numpy as jnp

    norms = jax.jit(lambda xs: [jnp.linalg.norm(x.astype(jnp.float32))
                                for x in xs])(arrays)
    keys = [leaf_key(adapter, n) for n in names]
    vec = {k: np.asarray(a, np.float32) * scale
           for k, a in zip(keys, arrays) if a.ndim == 1}
    return {k: float(v) * scale for k, v in zip(keys, norms)}, vec


def program_change(adapter, names, arrays, table, seed):
    """Per-leaf norm of (parameter now - seeded parameter). The seeded
    values are made again a layer at a time, so that the readout adds one
    layer's weights to the program's memory peak and not the whole model's."""
    import jax
    import jax.numpy as jnp

    diff = jax.jit(lambda x, y: jnp.linalg.norm(x.astype(jnp.float32)
                                                - y.astype(jnp.float32)))
    leaves = [adapter.leaf_of(n) for n in names]
    out = {}
    for layer in sorted({l for l, _ in leaves}, key=lambda l: (l is None, l)):
        w0 = (W.top_weights(table, seed, dtype=jnp.bfloat16) if layer is None
              else W.layer_weights(table, seed, layer, dtype=jnp.bfloat16))
        for n, (l, leaf), a in zip(names, leaves, arrays):
            if l == layer:
                out[leaf_key(adapter, n)] = float(diff(a, w0[leaf]))
    return out


def worst_gap(got: dict, want: dict) -> float:
    """Worst leaf: |got - want| over the larger of want and the median
    want (some leaves' norms are all but zero)."""
    med = float(np.median(list(want.values())))
    return max(abs(got[k] - want[k]) / max(want[k], med) for k in want)


def compare_training(prog: dict, ref: dict, limits: dict):
    out = [Compared(f"loss_gap_step{i + 1}",
                    abs(prog["loss"][i] - ref["loss"][i]),
                    float(limits["loss_gap"])) for i in range(2)]
    out.append(Compared("grad_norm_gap_worst_leaf",
                        worst_gap(prog["grad_norm"], ref["grad_norm"]),
                        float(limits["grad_norm_gap"])))
    med = float(np.median([np.linalg.norm(v)
                           for v in ref["gain_grad"].values()]))
    out.append(Compared("gain_grad_diff_worst_leaf", max(
        float(np.linalg.norm(prog["gain_grad"][k] - v))
        / max(float(np.linalg.norm(v)), med)
        for k, v in ref["gain_grad"].items()),
        float(limits["gain_grad_diff"])))
    out.append(Compared("change_norm_gap_worst_leaf",
                        worst_gap(prog["change_norm"], ref["change_norm"]),
                        float(limits["change_norm_gap"])))
    return out


def check_losses(losses, vocab: int, band: float):
    """Every step's loss in the window is finite and within ``band`` of
    ln(vocab): on fresh uniform ids there is nothing to learn but that."""
    bad = [x for x in losses if not np.isfinite(x)
           or abs(x - math.log(vocab)) > band]
    return Compared("window_losses_outside_band", float(len(bad)), 0.0)
