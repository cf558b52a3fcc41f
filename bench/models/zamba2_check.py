"""The comparison that decides `correct` for a Zamba2 cell.  Imports
nothing of the program.

Two numbers, each against the plain float32 reference (`zamba2_reference`,
products at `highest`), run layer-major over the same prompts and served
tokens once the window has closed and the engine is freed:

  greedy_gap  over a sample of finished greedy requests (the longest among
              them): at every served position, how far the reference's
              logit of the served token lies below the reference's best
              logit, the widest such gap as a share of the largest
              |reference logit| in the sample.  It covers the first-token
              head after bucket-padded chunked prefill, every decode tick
              and the head.
  state_err   over every request still in a slot when the window closed:
              the slot's SSM state h of every mamba layer, and the keys and
              values of every shared-block call at the positions the slot
              has filled, against the reference's after the same tokens;
              the root-mean-square difference as a share of the
              reference's root-mean-square, the worse of h and K/V
              (`state_err_max`, the same with largest values, beside it).

With `control=True` the same numbers are also read for the control: the
reference put in the program's place with its products one step below the
precision the configuration states (its `control`: "bfloat16", one pass).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import zamba2_data as DATA
from bench.models import zamba2_reference as REF


class _Err:
    """Running sums of a comparison, kept on the device: squared
    difference, squared reference, largest difference, largest
    reference."""

    def __init__(self):
        self.sums = jnp.zeros(4, jnp.float32)

    def add(self, prog, ref, mask=None):
        self.sums = _accumulate(self.sums, jnp.asarray(prog), ref,
                                True if mask is None else mask)

    def rms(self):
        d2, r2, _, _ = (float(x) for x in self.sums)
        return float(np.sqrt(d2 / max(r2, 1e-300)))

    def peak(self):
        _, _, dmax, rmax = (float(x) for x in self.sums)
        return dmax / max(rmax, 1e-300)


@jax.jit
def _accumulate(sums, prog, ref, mask):
    d = jnp.where(mask, prog - ref, 0.0)
    r = jnp.where(mask, ref, 0.0)
    return jnp.stack([sums[0] + jnp.sum(d * d), sums[1] + jnp.sum(r * r),
                      jnp.maximum(sums[2], jnp.max(jnp.abs(d))),
                      jnp.maximum(sums[3], jnp.max(jnp.abs(r)))])


def _greedy(lg, tokens, lo, hi):
    """Per greedy row: (widest gap, largest |logit|, positions agreeing,
    positions) over t in [lo, hi), logits at t predicting tokens[t+1]."""
    T = tokens.shape[1]
    t = jnp.arange(T)
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    inside = (t[None] >= lo[:, None]) & (t[None] < hi[:, None])
    best = jnp.max(lg, axis=-1)
    gap = best - jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
    return (jnp.max(jnp.where(inside, gap, 0.0), axis=1),
            jnp.max(jnp.where(inside[..., None], jnp.abs(lg), 0.0),
                    axis=(1, 2)),
            jnp.sum(inside & (gap == 0.0), axis=1), jnp.sum(inside, axis=1))


def check(cfg: dict, seed: int, *, greedy_rows, slot_rows, state,
          t_max: int, n_greedy: int, control: bool = False) -> dict:
    """greedy_rows: [(prompt, served tokens)]; slot_rows: [(prompt, tokens
    so far, slot)]; state: the pool read after the window (`h`, `k`, `v`,
    `pos`, zamba2.read_state); t_max: the longest prompt + output the
    traffic can make (the reference's fixed length, so it compiles once);
    n_greedy: the number of greedy rows it is compiled for."""
    D = DATA.dims(cfg)
    n_slots = state["h"].shape[1]
    R = n_greedy + n_slots
    tokens = np.zeros((R, t_max), np.int32)
    lo = np.zeros(n_greedy, np.int32)
    hi = np.zeros(n_greedy, np.int32)
    capture = np.full(R, -1, np.int32)
    if len(greedy_rows) > n_greedy:
        raise ValueError(f"{len(greedy_rows)} greedy rows, room for {n_greedy}")
    for r, (prompt, toks) in enumerate(greedy_rows):
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        tokens[r, :seq.size] = seq
        lo[r], hi[r] = len(prompt) - 1, len(prompt) - 1 + len(toks)
    bookkeeping_ok = True
    filled = {}
    for prompt, toks, slot in slot_rows:
        r = n_greedy + slot
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        pos = int(state["pos"][slot])
        if toks and pos != len(prompt) + len(toks) - 1:
            bookkeeping_ok = False  # the slot consumed other tokens
        if pos > seq.size:
            bookkeeping_ok = False
            continue
        if pos == 0:
            continue  # admitted, no prompt chunk run yet: nothing to compare
        tokens[r, :seq.size] = seq
        capture[r] = pos - 1
        filled[slot] = pos
    slots = sorted(filled)
    rows = np.array([n_greedy + s for s in slots], np.int64)

    # the reference runs at the longest row, rounded up to 256 tokens (a
    # few shapes compile, whatever the rows)
    longest = max([len(p) + len(t) for p, t in greedy_rows]
                  + [filled[s] + 1 for s in slots] + [1])
    T = min(t_max, -(-longest // 256) * 256)
    tokens = tokens[:, :T]
    toks = jnp.asarray(tokens)
    cap_at = jnp.asarray(capture)
    streams = {"ref": REF.Stream(cfg, seed, toks, cap_at)}
    if control:
        streams["ctl"] = REF.Stream(cfg, seed, toks, cap_at,
                                    REF.CONTROLS[cfg["control"]])
    err = {name: {"h": _Err(), "kv": _Err()} for name in streams}
    ids = D["ids"]
    sel = jnp.asarray(rows)
    # positions each compared slot has filled, (slots, T, 1)
    kv_mask = jnp.asarray(np.arange(T)[None, :, None]
                          < np.array([filled[s] for s in slots])[:, None,
                                                                   None])
    for i in range(D["layers"]):
        out = {name: s.step(i) for name, s in streams.items()}
        caps = {name: o[0][sel] for name, o in out.items()}
        if not slots:
            continue
        # program h is (slots, H, N, P); the reference's (R, H, P, N)
        prog_h = np.swapaxes(state["h"][i][slots], -1, -2)
        err["ref"]["h"].add(prog_h, caps["ref"])
        if control:
            err["ctl"]["h"].add(caps["ctl"], caps["ref"])
        if i in ids:
            c = ids.index(i)
            ref_kv = [a[sel] for a in out["ref"][1]]
            for a, which in enumerate(("k", "v")):
                prog = np.zeros((len(slots), T, state[which].shape[-1]),
                                np.float32)
                n = min(T, state[which].shape[2])
                prog[:, :n] = state[which][c][slots][:, :n]
                err["ref"]["kv"].add(prog, ref_kv[a], kv_mask)
                if control:
                    err["ctl"]["kv"].add(out["ctl"][1][a][sel], ref_kv[a],
                                         kv_mask)
        del out

    g = len(greedy_rows)
    res = {"greedy_rows": g, "slot_rows": len(slots)}
    scale = 0.0
    if g:
        gr = jnp.arange(n_greedy)
        lg = streams["ref"].logits(gr)
        gap, top, agree, count = jax.device_get(
            _greedy(lg, toks[:n_greedy], jnp.asarray(lo), jnp.asarray(hi)))
        scale = float(top[:g].max())
        res["greedy_gap"] = (float(gap[:g].max()) / scale if scale > 0
                             else float("inf"))
        res["greedy_tokens"] = int(count[:g].sum())
        res["greedy_tokens_agree"] = int(agree[:g].sum())
    else:
        res.update(greedy_gap=0.0, greedy_tokens=0, greedy_tokens_agree=0)
    res["logit_scale"] = scale

    def worst(e, f):
        return max(f(e["h"]), f(e["kv"])) if slots else 0.0

    ok = bookkeeping_ok
    res["state_err"] = worst(err["ref"], _Err.rms) if ok else float("inf")
    res["state_err_max"] = worst(err["ref"], _Err.peak) if ok else \
        float("inf")
    if control:
        ctl = {"state_err": worst(err["ctl"], _Err.rms),
               "state_err_max": worst(err["ctl"], _Err.peak),
               "greedy_gap": 0.0}
        if g:
            lc = streams["ctl"].logits(jnp.arange(n_greedy))
            pick = jnp.argmax(lc, axis=-1)
            best = jnp.max(lg, axis=-1)
            gc = best - jnp.take_along_axis(lg, pick[..., None], -1)[..., 0]
            t = jnp.arange(T)
            inside = ((t[None] >= jnp.asarray(lo)[:, None])
                      & (t[None] < jnp.asarray(hi)[:, None]))
            ctl["greedy_gap"] = float(jnp.max(jnp.where(inside, gc, 0.0)
                                              [:g])) / scale
        res["control"] = ctl
    return res
