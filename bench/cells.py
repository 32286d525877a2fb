"""Cells, configurations and traffic, all read from data files by name.

A cell (``workloads/<cell>.json``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``)
and holds the serving parameters of the cell.  A configuration names its
model family (``families/<family>.py``; ``families/__init__.py`` says what
a family gives).  Adding a cell, a configuration, a traffic mix or a model
family adds files here and edits none.

One generator turns a traffic file into closed waves of requests.  A file
gives each length (``prefix``, ``query``, ``max_new``) as a distribution:
``uniform`` or ``lognormal`` between ``min`` and ``max``, or ``choice``
over listed ``values`` with optional ``weights`` (a published histogram),
each rounded up to an optional ``multiple``.  An optional ``sharing``
(``{"contexts": k, "zipf": a}``) draws every request's context from ``k``
sessions with Zipf(a) popularity: a session's prefix length and tokens are
the same in every request and every wave that uses it.

Every wave of a cell carries the cell's longest shared prefix, its longest
query bucket and its longest reply, so every wave builds the same
slot-table geometry and reuses the same compiled programs.  Every wave has
the same sizes in the same order, drawn once from the traffic file's
``size_seed``: the work of a run is fixed, and ``--seed`` draws only the
token ids.  The scheduler's drain stacks one array per iteration, which
compiles once per iteration count, and the iteration count of a wave
depends on its sizes and on their order; so a template drawn per seed, or
reordered per seed, would compile inside the window.
"""
from __future__ import annotations

import dataclasses
import importlib
import json
import math
import os
from typing import Dict, List, Tuple

import numpy as np

SESSION_TAG = 1 << 41       # token-id stream tag of shared session contexts
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def _load(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """The cell's file, with its configuration and traffic files merged in
    under ``config_file`` and ``traffic_file``."""
    cell = _load("workloads", name)
    cell["name"] = name
    cell["config_file"] = _load("configs", cell["config"])
    cell["traffic_file"] = _load("traffic", cell["traffic"])
    return cell


def family(conf: dict):
    """The module of the configuration's model family: ``families/<name>.py``
    for the file's ``family`` (``"llama"`` where it names none), found on the
    ``families`` package's path.  An unknown name is an error."""
    import families
    name = conf.get("family", "llama")
    if not name.isidentifier():
        raise ValueError(f"model family {name!r} is not a module name")
    try:
        return importlib.import_module(f"families.{name}")
    except ModuleNotFoundError as e:
        if e.name != f"families.{name}":
            raise
        raise ValueError(f"unknown model family {name!r}: no {name}.py on "
                         f"{list(families.__path__)}") from None


@dataclasses.dataclass(frozen=True)
class Size:
    """One request's lengths: shared prefix (BOS + context), query, reply;
    and the session whose context it reuses (-1: a context of its own)."""
    prefix: int
    query: int
    max_new: int
    session: int = -1


def _draw(rng: np.random.Generator, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] == "uniform":
        x = rng.integers(spec["min"], spec["max"] + 1, n)
    elif spec["dist"] == "lognormal":
        x = np.exp(rng.normal(math.log(spec["median"]), spec["sigma"], n))
    elif spec["dist"] == "choice":
        w = np.asarray(spec.get("weights", [1] * len(spec["values"])), float)
        x = rng.choice(np.asarray(spec["values"]), n, p=w / w.sum())
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    if spec["dist"] != "choice":
        x = np.clip(x, spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    return (np.ceil(x / m) * m).astype(np.int64)


def lengths(spec: dict) -> List[int]:
    """Every length a spec can draw."""
    m = spec.get("multiple", 1)
    up = lambda v: int(math.ceil(v / m) * m)
    if spec["dist"] == "choice":
        return sorted({up(v) for v in spec["values"]})
    return list(range(up(spec["min"]), up(spec["max"]) + 1, m))


def _largest(spec: dict) -> int:
    return lengths(spec)[-1]


def wave_sizes(traffic: dict, wave: int) -> List[Size]:
    """The sizes of every wave: drawn from the traffic's distributions, with
    the three largest values each put on one request, in a fixed order."""
    rng = np.random.default_rng(traffic["size_seed"])
    p = _draw(rng, traffic["prefix"], wave)
    q = _draw(rng, traffic["query"], wave)
    n = _draw(rng, traffic["max_new"], wave)
    slots = rng.permutation(wave)[:3]
    p[slots[0]] = _largest(traffic["prefix"])
    q[slots[1]] = _largest(traffic["query"])
    n[slots[2]] = _largest(traffic["max_new"])
    sess = np.full(wave, -1)
    if "sharing" in traffic:
        k, a = traffic["sharing"]["contexts"], traffic["sharing"]["zipf"]
        plen = _draw(rng, traffic["prefix"], k)
        plen[0] = _largest(traffic["prefix"])
        pop = 1.0 / np.arange(1, k + 1) ** a
        sess = rng.choice(k, wave, p=pop / pop.sum())
        sess[slots[0]] = 0
        p = plen[sess]
    return [Size(int(a), int(b), int(c), int(d))
            for a, b, c, d in zip(p, q, n, sess)]


def _pairs(sizes: List[Size], query_bucket: int) -> set:
    b = lambda n: -(-n // query_bucket) * query_bucket
    return {(s.prefix, b(s.query)) for s in sizes}


def warm_waves(cell: dict) -> List[List[Size]]:
    """The waves set-up serves so that the window compiles nothing: one
    wave as the window serves it (the scheduler's drain compiles per
    iteration count), after a wave of every (prefix length, query bucket)
    pair the traffic can draw where that one lacks some, or leaves a slot
    of the table unfilled."""
    wave = wave_sizes(cell["traffic_file"], cell["wave"])
    cover = warm_sizes(cell)
    if (_pairs(cover, cell["query_bucket"])
            <= _pairs(wave, cell["query_bucket"])
            and len(wave) > cell["capacity"]):
        return [wave]
    return [cover, wave]


def warm_sizes(cell: dict) -> List[Size]:
    """A wave of every (prefix length, query bucket) pair the traffic can
    draw, each slot of the table filled at least once, and the cell's
    maxima."""
    t = cell["traffic_file"]
    qb = cell["query_bucket"]
    qmax = _largest(t["query"])
    qlens = sorted({min(qmax, max(lengths(t["query"])[0], b))
                    for b in range(qb, qmax + qb, qb)})
    nmax = _largest(t["max_new"])
    sizes = [Size(_largest(t["prefix"]), qmax, nmax)]
    sizes += [Size(p, q, 2) for p in lengths(t["prefix"]) for q in qlens]
    while len(sizes) < cell["capacity"] + 1:
        sizes.append(Size(sizes[1].prefix, qmax, 2))
    return sizes


def requests(sizes: List[Size], vocab: int, seed: int, tag: int,
             first_rid: int = 0):
    """``Request``s of the given sizes with token ids drawn uniformly from
    the vocabulary by (seed, tag), a session's context by (seed, session)
    alone; the context excludes the BOS the sender prepends."""
    from repro.serving.scheduler import Request
    rng = np.random.default_rng([seed, tag])
    out = []
    for i, s in enumerate(sizes):
        ctx = rng.integers(0, vocab, s.prefix - 1).astype(np.int32)
        if s.session >= 0:
            ctx = np.random.default_rng([seed, SESSION_TAG, s.session]) \
                .integers(0, vocab, s.prefix - 1).astype(np.int32)
        out.append(Request(
            rid=first_rid + i, context=ctx,
            query=rng.integers(0, vocab, s.query).astype(np.int32),
            max_new=s.max_new))
    return out


def calibration_sample(conf: dict) -> Tuple[np.ndarray, np.ndarray]:
    """The fixed calibration (context, query) of a configuration."""
    c = conf["calibration"]
    rng = np.random.default_rng(c["seed"])
    ctx = rng.integers(0, conf["vocab_size"], c["context_tokens"])
    qry = rng.integers(0, conf["vocab_size"], c["query_tokens"])
    return ctx.astype(np.int32), qry.astype(np.int32)


def benchmark_entry(name: str) -> Dict:
    """The cell's entry in ``BENCHMARK.json``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")
