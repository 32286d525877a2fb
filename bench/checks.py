"""Whether what the timed path served is correct.

After the window, a sample of the finished requests, drawn from the seed
and always holding the longest reply, is run through the plain reference
of the configuration's family (``cells.family``) over each prompt with its
served tokens.  Two numbers are compared, each with a limit from the
cell's file:

* ``max_logit_gap``: the widest gap, over every served token of the
  sample, by which the reference's logit of the served token lies below the
  reference's best logit at that position.  Greedy serving in the
  configuration's precision reads a rounding-sized gap; a token changed on
  its way reads a gap of the size of the logits' spread.
* ``selection_mismatch``: how many layers differ between the program's
  frozen selection and the one the reference calibrates from the same
  sample (exact: the limit is 0).

With ``control``, the readings also hold ``control``: the same numbers
read for the control on the same sample, the reference computed with
float8 products in the program's place (its gap is that of the token it
puts first at each position; it takes the reference's selection).
``verdict`` judges both alike, and the control has to come out not
correct.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax.numpy as jnp
import numpy as np

import cells


def sample(waves, seed: int, count: int):
    """``count`` (request, served tokens) pairs: the longest reply, and
    others drawn from the seed."""
    done = [(r, c.tokens) for w in waves
            for r, c in zip(sorted(w.requests, key=lambda r: r.rid),
                            w.completions)]
    longest = max(range(len(done)), key=lambda i: len(done[i][1]))
    rest = [i for i in np.random.default_rng([seed, 17]).permutation(
        len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:count - 1]]


def _gap(logits, chosen):
    lg = np.asarray(logits, np.float64)
    best = lg.max(axis=-1)
    return best - lg[np.arange(len(chosen)), np.asarray(chosen)]


def compare(bench, picked, control: bool = False) -> Dict[str, object]:
    """Run the reference (and, with ``control``, the float8 control) over
    the picked requests; return the readings."""
    conf, cell = bench.conf, bench.cell
    fam = cells.family(conf)
    g = fam.Geometry(conf)
    bos = conf["bos_token_id"]
    ctx, qry = cells.calibration_sample(conf)
    layers, _ = fam.selection(bench.params, g, ctx, qry, bos,
                              cell["ratio"], cell["alpha"])
    mismatch = len(set(layers) ^ set(bench.layers))
    pad_to = max(s.prefix for s in bench.sizes)
    gaps, cgaps, served = [], [], 0
    for req, toks in picked:
        toks = np.asarray(toks)
        served += len(toks)
        lg = fam.hop_logits(bench.params, g, layers, bos, req.context,
                            req.query, toks, pad_to)
        if not bool(jnp.all(jnp.isfinite(lg))):
            gaps.append(np.inf)
            continue
        gaps.append(float(_gap(lg, toks).max()))
        if control:
            cl = fam.hop_logits(bench.params, g, layers, bos, req.context,
                                req.query, toks, pad_to, policy="fp8")
            top = np.asarray(jnp.argmax(cl, axis=-1))
            cgaps.append(float(_gap(lg, top).max()))
        del lg
    out = {"max_logit_gap": max(gaps), "selection_mismatch": mismatch,
           "requests": len(picked), "served_tokens": served,
           "reference_layers": list(layers)}
    if control:
        out["control"] = {"max_logit_gap": max(cgaps) if cgaps else np.inf,
                          "selection_mismatch": 0}
    return out


def verdict(readings: Dict[str, object], limits: Dict[str, float]
            ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
    """(correct, {number: {value, limit}}) for every number with a limit."""
    table = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return bool(ok), table
