"""The comparison that decides ``correct``.

Once the window has closed, a sample of the requests it finished is drawn
from the seed, the longest among them.  For every served token of every
sample of those requests the plain float32 reference of the model's
family (``bench/families``; the dense one's is ``bench/reference``) is
run over the prompt and the served tokens before it, and the token is
judged by the *gap*: how far its perturbed score lies below the best
perturbed score the reference allows.

The traffic samples its tokens (temperature, top-k, top-p), which the
program does as ``argmax(masked logits / T + gumbel(key))`` with one key
per scheduler step and one noise row per batch row.  The harness chose
those keys, so the reference redraws the same noise for the row and step
that produced each token and applies its own top-k/top-p mask to its own
logits: a sound program's token is the reference's choice, or loses to
it only by rounding.  Gaps are in logit units (scores times T).

The control puts the reference itself in the program's place in the next
precision below the configuration's bf16 (the family's fp8 control, see
``reference.model``): at each position it reads the gap of the token the
fp8 forward puts first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import generator as gen_mod

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 32          # positions per reference logits block
# the longest finished request and others drawn from the seed: enough that
# the rows compared lie in both halves of the batch
N_REQUESTS = 8


def finished_in_window(sched, tracer, items: dict, t0: float, t1: float) -> list:
    """Request ids whose last sample was released inside the window."""
    last = {}
    for ev in tracer.events:
        if ev.kind == "release" and t0 <= ev.t <= t1:
            last[ev.req_id] = ev.t
    return [rid for rid in last
            if len(sched.completed.get(rid, [])) == items[rid].n_samples]


def failed(sched, items: dict, finished: list) -> int:
    """Finished requests with a sample that did not serve its length."""
    bad = 0
    for rid in finished:
        want = items[rid].max_new_tokens
        if any(len(s.tokens) != want or s.finish_reason != "length"
               or s.finished_step - s.first_decode_step + 1 != want
               for s in sched.completed[rid]):
            bad += 1
    return bad


def pick(finished: list, sched, tracer, items: dict, seed: int) -> list:
    """The requests compared: the longest finished one and others drawn
    from the seed, with what the reference needs of each."""
    if not finished:
        return []
    order = sorted(finished, key=lambda r: (-items[r].max_new_tokens, r))
    rest = order[1:]
    rng = gen_mod.stream(seed, gen_mod.CHECK)
    chosen = [order[0]] + [rest[i] for i in sorted(
        rng.choice(len(rest), min(len(rest), N_REQUESTS - 1), replace=False))]
    rows = {}
    for ev in tracer.events:
        if ev.kind in ("admit", "readmit") and ev.req_id in chosen:
            rows[ev.req_id] = list(ev.args["rows"])
    out = []
    for rid in chosen:
        samples = sorted(sched.completed[rid], key=lambda s: s.sample_idx)
        out.append({
            "req_id": rid,
            "prompt": np.asarray(items[rid].prompt, np.int32),
            "rows": [rows[rid][s.sample_idx] for s in samples],
            "first_step": [s.first_decode_step for s in samples],
            "tokens": [list(s.tokens) for s in samples],
        })
    return out


# per-logit shifts tried, in logit units; a gap is twice the least shift
# that explains a token, so a greedy token's gap is best - served
SHIFTS = np.concatenate([[0.0], np.geomspace(1e-4, 4.0, 63)])
UNEXPLAINED = 1e3   # no shift up to 4 logits explains the token
EXTRA = 64          # candidates beyond the top-k that a shift can promote


def _candidates(logits, noise, sp):
    """What the sampler sees: scaled logits x, the noise it adds, the
    best candidates' ids, values, perturbed scores and nucleus
    probabilities, its (k, p), and the temperature.  Greedy is top-1
    without noise."""
    temp, top_k, top_p, greedy = sp
    if greedy:
        temp, top_k, top_p, noise = 1.0, 1, 1.0, jnp.zeros_like(noise)
    x = logits / max(temp, 1e-6)
    k = top_k or min(256, x.shape[-1])
    vals, idx = jax.lax.top_k(x, min(k + EXTRA, x.shape[-1]))
    lz = (jax.nn.logsumexp(vals[..., :top_k], axis=-1, keepdims=True)
          if top_k else jax.nn.logsumexp(x, axis=-1, keepdims=True))
    probs = jnp.exp(vals - lz)
    scores = vals + jnp.take_along_axis(noise, idx, -1)
    return x, noise, idx, vals, scores, probs, top_k or x.shape[-1], top_p, temp


def _gap(logits, noise, tok, sp):
    """Twice the least per-logit shift of the reference's logits under
    which the sampler, with the same noise, could pick ``tok``: it must be
    able to enter the top-k and the nucleus, and its score must reach that
    of every candidate that surely stays in both."""
    x, noise, idx, vals, scores, probs, k, p, temp = \
        _candidates(logits, noise, sp)
    xs = jnp.take_along_axis(x, tok[..., None], -1)[..., 0]
    ss = xs + jnp.take_along_axis(noise, tok[..., None], -1)[..., 0]
    cum = jnp.cumsum(probs, axis=-1)
    others = idx != tok[..., None]

    def explains(shift):
        d = shift / temp
        # tokens certainly above tok after the shift
        above = vals > (xs + 2 * d)[..., None]
        m_above = jnp.sum(jnp.where(above, probs, 0.0), axis=-1)
        can_enter = (jnp.sum(above, -1) < k) & (jnp.exp(-2 * d) * m_above < p)
        # candidates certainly kept: few enough tokens can reach them (the
        # values are sorted, so those that can form a prefix)
        n_reach = jnp.sum(vals[..., None, :] >= vals[..., :, None] - 2 * d, -1)
        m_reach = jnp.take_along_axis(cum, n_reach - 1, -1) - probs
        sure = (n_reach <= k) & (jnp.exp(2 * d) * m_reach < p) & others
        best = jnp.max(jnp.where(sure, scores, -jnp.inf), axis=-1)
        return can_enter & (best - ss <= 2 * d)

    ok = jnp.moveaxis(jax.lax.map(explains, jnp.asarray(SHIFTS, jnp.float32)),
                      0, -1)
    first = jnp.argmax(ok, axis=-1)
    return jnp.where(jnp.any(ok, axis=-1),
                     2 * jnp.asarray(SHIFTS, jnp.float32)[first], UNEXPLAINED)


def _choice(logits, noise, sp):
    """The token the sampler picks from ``logits`` with this noise."""
    _, _, idx, vals, scores, probs, k, p, _ = _candidates(logits, noise, sp)
    mass_above = jnp.cumsum(probs, axis=-1) - probs
    keep = (jnp.arange(vals.shape[-1]) < k) & (mass_above < p)
    j = jnp.argmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
    return jnp.take_along_axis(idx, j[..., None], -1)[..., 0]


@partial(jax.jit, static_argnames=("n_slots", "sp", "control"))
def _request_gaps(head, ctrl_head, H, Hc, keys, rows, toks, *, n_slots, sp,
                  control):
    """Gaps of one request's samples, CHUNK positions at a time.

    H, Hc: (n, P, d) reference and control hidden rows; keys (P, 2) the
    step keys of each position; rows (n,); toks (n, P) served tokens.
    Returns (served gaps (n, P), control gaps (n, P))."""
    n, P, _ = H.shape
    V = head.shape[0]

    def chunk(c):
        hs = jax.lax.dynamic_slice_in_dim(H, c * CHUNK, CHUNK, axis=1)
        ks = jax.lax.dynamic_slice_in_dim(keys, c * CHUNK, CHUNK, axis=0)
        tk = jax.lax.dynamic_slice_in_dim(toks, c * CHUNK, CHUNK, axis=1)
        noise = jax.lax.map(
            lambda k: jax.random.gumbel(k, (n_slots, V), jnp.float32)[rows], ks)
        noise = jnp.swapaxes(noise, 0, 1)                    # (n, C, V)
        logits = jnp.einsum("ncd,vd->ncv", hs, head, precision=HIGHEST)
        served = _gap(logits, noise, tk, sp)
        if not control:
            return served, jnp.zeros_like(served)
        hc = jax.lax.dynamic_slice_in_dim(Hc, c * CHUNK, CHUNK, axis=1)
        lc = jnp.einsum("ncd,vd->ncv", hc, ctrl_head, precision=HIGHEST)
        return served, _gap(logits, noise, _choice(lc, noise, sp), sp)

    g, gc = jax.lax.map(chunk, jnp.arange(P // CHUNK))
    return (jnp.moveaxis(g, 0, 1).reshape(n, P),
            jnp.moveaxis(gc, 0, 1).reshape(n, P))


def hidden_rows(params, m, prompt, tokens, max_len, P, weights, family):
    """Reference hidden states at the positions that produced ``tokens``:
    position plen-1+t gives the logits of token t."""
    plen = len(prompt)
    seq = np.zeros((max_len,), np.int32)
    full = np.concatenate([prompt, np.asarray(tokens[:-1], np.int32)])
    seq[:len(full)] = full
    pos = np.clip(plen - 1 + np.arange(P), 0, max_len - 1).astype(np.int32)
    return jnp.take(family.hidden(params, seq, m, weights), pos, axis=0)


def request_gaps(params, heads, config, mix, req, keys, control: bool,
                 family):
    m = config["model"]
    s = config["sampler"]
    sp = (float(s["temperature"]), int(s["top_k"]), float(s["top_p"]),
          bool(s.get("greedy", False)))
    P = -(-mix["output_tokens"]["max"] // CHUNK) * CHUNK
    n = len(req["tokens"])
    H = jnp.stack([hidden_rows(params, m, req["prompt"], t, mix["max_len"], P,
                               "f32", family) for t in req["tokens"]])
    Hc = (jnp.stack([hidden_rows(params, m, req["prompt"], t, mix["max_len"],
                                 P, "fp8", family) for t in req["tokens"]])
          if control else H)
    if len(set(req["first_step"])) != 1:
        raise ValueError("samples of one request decoded at different steps")
    # token t of every sample was drawn at step first + t
    step_keys = keys[np.minimum(req["first_step"][0] + np.arange(P),
                                len(keys) - 1)]
    toks = np.zeros((n, P), np.int32)
    valid = np.zeros((n, P), bool)
    for i, t in enumerate(req["tokens"]):
        toks[i, :len(t)] = t
        valid[i, :len(t)] = True
    g, gc = _request_gaps(heads[0], heads[1], H, Hc, jnp.asarray(step_keys),
                          jnp.asarray(req["rows"], jnp.int32),
                          jnp.asarray(toks), n_slots=config["slots"], sp=sp,
                          control=control)
    g, gc = np.asarray(g), np.asarray(gc)
    return g[valid], gc[valid]


def run_check(params, config, mix, picked, keys, n_failed: int, family,
              control: bool = False) -> dict:
    """Compare the picked requests with the reference of the model's
    ``family``; with ``control`` also read the fp8 control's gaps on the
    same tokens."""
    heads = (family.head(params, "f32"),
             family.head(params, "fp8") if control else None)
    served, ctrl = [], []
    for req in picked:
        g, gc = request_gaps(params, heads, config, mix, req, keys, control,
                             family)
        served.append(g)
        ctrl.append(gc)
    served = np.concatenate(served) if served else np.zeros((0,))
    limit = config["check_limits"]["max_gap_logit"]
    widest = float(served.max()) if served.size else UNEXPLAINED
    numbers = {
        "max_gap_logit": {"value": widest, "limit": limit},
        "failed_requests": {"value": n_failed, "limit": 0},
    }
    def verdict(gaps, widest):
        return bool(gaps.size and limit is not None and widest <= limit
                    and n_failed == 0)

    out = {
        "correct": verdict(served, widest),
        "numbers": numbers,
        "tokens_compared": int(served.size),
        "tokens_differing": int((served > 0).sum()),
    }
    if control:
        ctrl = np.concatenate(ctrl) if ctrl else np.zeros((0,))
        c_widest = float(ctrl.max()) if ctrl.size else UNEXPLAINED
        out["control_max_gap_logit"] = c_widest
        out["control_tokens_differing"] = int((ctrl > 0).sum())
        # the control judged by the same comparison: it must come out false
        out["control_correct"] = verdict(ctrl, c_widest)
    return out
