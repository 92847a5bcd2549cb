"""How ``correct`` is decided: what the timed path served, against the plain
float32 reference under ``reference/``.

After the window closes, a sample of the finished requests is drawn from
the seed, the longest among them.  Two numbers are compared, each with the
limit in ``limits/<cell>.json``:

- ``served_gap``: over every served token of the sample, the widest gap by
  which its reference logit lies below the reference's best at that
  position.  The reference runs once over each prompt with its served
  tokens.  It covers prefill, then decoding through the cache, at the
  modes the cell's plan chose.  A greedy token that the program got right
  reads 0.
- ``prefill_err``: the prefill program the window drove, run again on each
  sampled prompt, against the reference's logits at the prompt's last
  position: the largest absolute difference over the largest reference
  logit.  It separates precisions that rarely flip a greedy token.

A control (``control_readings``) puts the reference, computed a step below
the configuration's precision, in the program's place: its token at each
position is the one the lower precision puts first.
"""
from __future__ import annotations

import dataclasses
import importlib
import types

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import WINDOW, seed_rng

ROW_BLOCK = 256


def reference_module(family: str):
    return importlib.import_module(f"chipbench.reference.{family}")


def ref_dims(arch_cfg, config: dict) -> types.SimpleNamespace:
    """The sizes a reference reads, copied from the configuration as run
    (the program's ArchConfig ``arch_cfg``), and under ``published`` the
    cell's configuration file ``config`` as loaded: the source's constants
    by its own keys (``chipbench.reference.common``)."""
    fields = {f.name: getattr(arch_cfg, f.name) for f in dataclasses.fields(arch_cfg)
              if f.name != "policy"}
    return types.SimpleNamespace(**fields, published=config)


def sample(records: dict, seed: int, tokens: int, max_requests: int) -> list:
    """Finished requests due in the window (any finished one for a
    backlog), drawn from the seed: the longest first, then at random until
    ``tokens`` served tokens or ``max_requests`` requests."""
    done = [r for r in records.values() if r.done and r.arrival.segment == WINDOW]
    if not done:
        return []
    done.sort(key=lambda r: r.arrival.rid)
    longest = max(done, key=lambda r: (r.arrival.prompt_len + len(r.tokens), -r.arrival.rid))
    rest = [r for r in done if r is not longest]
    order = seed_rng(seed, 3).permutation(len(rest))
    out, n = [longest], len(longest.tokens)
    for i in order:
        if n >= tokens or len(out) >= max_requests:
            break
        out.append(rest[i])
        n += len(rest[i].tokens)
    return out


class Reference:
    """One compiled reference over sequences padded to ``pad_len``."""

    def __init__(self, family: str, dims, pad_len: int):
        self.mod = reference_module(family)
        self.dims = dims
        self.pad_len = pad_len
        self._hidden = jax.jit(
            lambda params, tokens, prompt_len, precision: self.mod.hidden(
                params, dims, tokens, prompt_len, precision),
            static_argnums=(3,))
        self._logits = jax.jit(self._rows, static_argnums=(3,))

    @staticmethod
    def _rows(h, w, idx, precision):
        from chipbench.reference.common import logits_rows

        return logits_rows(h[idx], w, precision)

    def hidden(self, params, tokens, prompt_len, precision="highest"):
        padded = np.zeros((self.pad_len,), np.int32)
        padded[:len(tokens)] = tokens
        return self._hidden(params, jnp.asarray(padded), jnp.int32(prompt_len), precision)

    def logits(self, params, h, rows, precision="highest"):
        """Logits (len(rows), V) in blocks of ``ROW_BLOCK`` rows."""
        w = self.mod.head(params, self.dims)
        out = []
        for i in range(0, len(rows), ROW_BLOCK):
            blk = np.full((ROW_BLOCK,), rows[i], np.int32)
            part = rows[i:i + ROW_BLOCK]
            blk[:len(part)] = part
            out.append(np.asarray(self._logits(h, w, jnp.asarray(blk), precision))[:len(part)])
        return np.concatenate(out)


def readings(ref: Reference, params, sampled: list, prefill_logits: list,
             control: str | None = None) -> dict:
    """The compared numbers of one run; with ``control`` also the control's
    readings at that precision (``control_served_gap``,
    ``control_prefill_err``)."""
    gap = err = 0.0
    c_gap = c_err = 0.0
    n_tokens = 0
    for rec, plog in zip(sampled, prefill_logits):
        p = rec.arrival.prompt_len
        seq = np.concatenate([rec.prompt, np.asarray(rec.tokens[:-1], np.int32)])
        rows = np.arange(p - 1, p - 1 + len(rec.tokens))
        h = ref.hidden(params, seq, p)
        lg = ref.logits(params, h, rows)
        served = np.asarray(rec.tokens)
        best = lg.max(axis=1)
        gap = max(gap, float((best - lg[np.arange(len(rows)), served]).max()))
        scale = float(np.abs(lg[0]).max())
        err = max(err, float(np.abs(plog - lg[0]).max()) / scale)
        n_tokens += len(rows)
        if control is not None:
            hc = ref.hidden(params, seq, p, control)
            lc = ref.logits(params, hc, rows, control)
            pick = lc.argmax(axis=1)
            c_gap = max(c_gap, float((best - lg[np.arange(len(rows)), pick]).max()))
            c_err = max(c_err, float(np.abs(lc[0] - lg[0]).max()) / scale)
    out = {"served_gap": gap, "prefill_err": err, "sampled_requests": len(sampled),
           "sampled_tokens": n_tokens}
    if control is not None:
        out.update(control_served_gap=c_gap, control_prefill_err=c_err)
    return out


COMPARED = ("served_gap", "prefill_err")


def verdict(values: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and the compared numbers beside their limits."""
    compared = [(k, values[k], float(limits[k])) for k in COMPARED]
    ok = values["sampled_requests"] > 0 and all(v <= lim for _, v, lim in compared)
    return ok, compared


def control_verdict(values: dict, limits: dict) -> tuple[bool, list]:
    """What ``correct`` would read with the control in the program's place:
    the control's readings judged by the same limits."""
    return verdict(dict(values, **{k: values[f"control_{k}"] for k in COMPARED}), limits)
