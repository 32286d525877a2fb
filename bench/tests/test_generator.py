"""The traffic generator: deterministic, only the declared lengths, one
table geometry in every wave, and a set-up wave that covers every shape."""
import numpy as np
import pytest

import cells

CELLS = ["longdoc.internlm2-20b-d8", "reply.deepseek-llm-7b-d10"]


def _bucket(n, m):
    return -(-n // m) * m


@pytest.mark.parametrize("name", CELLS)
def test_waves_are_fixed_and_declared(name):
    cell = cells.load_cell(name)
    t = cell["traffic_file"]
    sizes = cells.wave_sizes(t, cell["wave"])
    assert sizes == cells.wave_sizes(t, cell["wave"])
    assert len(sizes) == cell["wave"]
    assert {s.prefix for s in sizes} <= set(cells.lengths(t["prefix"]))
    assert all(t["query"]["min"] <= s.query <= t["query"]["max"]
               for s in sizes)
    assert all(t["max_new"]["min"] <= s.max_new <= t["max_new"]["max"]
               for s in sizes)
    # the geometry every wave builds: the three maxima are always there
    assert max(s.prefix for s in sizes) == max(cells.lengths(t["prefix"]))
    assert max(s.query for s in sizes) == t["query"]["max"]
    assert max(s.max_new for s in sizes) == t["max_new"]["max"]


@pytest.mark.parametrize("name", CELLS + ["shared"])
def test_warm_wave_covers_every_shape(name):
    cell = cells.load_cell(name) if name != "shared" else {
        "traffic_file": SHARED, "wave": 12, "capacity": 3,
        "query_bucket": 8}
    t, qb = cell["traffic_file"], cell["query_bucket"]
    warm = cells.warm_sizes(cell)
    pairs = {(s.prefix, _bucket(s.query, qb)) for s in warm}
    drawn = {(s.prefix, _bucket(s.query, qb))
             for s in cells.wave_sizes(t, cell["wave"])}
    assert drawn <= pairs
    assert len(warm) > cell["capacity"]
    wave = cells.wave_sizes(t, cell["wave"])
    geometry = lambda ss: (max(s.prefix for s in ss),
                           _bucket(max(s.query for s in ss), qb),
                           max(s.max_new for s in ss))
    assert geometry(warm) == geometry(wave)


def test_token_ids_follow_the_seed():
    cell = cells.load_cell(CELLS[0])
    sizes = cells.wave_sizes(cell["traffic_file"], cell["wave"])
    a = cells.requests(sizes, 92544, 2**31 + 5, 0)
    b = cells.requests(sizes, 92544, 2**31 + 5, 0)
    c = cells.requests(sizes, 92544, 2**31 + 6, 0)
    assert all(np.array_equal(x.context, y.context) for x, y in zip(a, b))
    assert not all(np.array_equal(x.context, y.context)
                   for x, y in zip(a, c))
    assert [len(r.context) + 1 for r in a] == [s.prefix for s in sizes]
    assert all(r.context.max() < 92544 for r in a)


SHARED = {"prefix": {"dist": "choice", "values": [100, 300, 700],
                     "weights": [3, 2, 1], "multiple": 256},
          "query": {"dist": "choice", "values": [5, 12, 20]},
          "max_new": {"dist": "choice", "values": [4, 9]},
          "sharing": {"contexts": 3, "zipf": 1.2},
          "size_seed": 11}


def test_choice_draws_only_listed_values():
    assert cells.lengths(SHARED["prefix"]) == [256, 512, 768]
    sizes = cells.wave_sizes({k: v for k, v in SHARED.items()
                              if k != "sharing"}, 40)
    assert {s.prefix for s in sizes} <= {256, 512, 768}
    assert {s.max_new for s in sizes} == {4, 9}
    assert all(s.session == -1 for s in sizes)


def test_shared_sessions_repeat_their_context():
    sizes = cells.wave_sizes(SHARED, 12)
    assert {s.session for s in sizes} <= {0, 1, 2}
    assert max(s.prefix for s in sizes) == 768
    by_session = {}
    for s in sizes:
        assert by_session.setdefault(s.session, s.prefix) == s.prefix
    a = cells.requests(sizes, 500, 2**31 + 5, 0)
    b = cells.requests(sizes, 500, 2**31 + 5, 1, first_rid=12)
    first = {}
    for s, r in zip(sizes + sizes, a + b):
        ctx = first.setdefault(s.session, r.context)
        assert np.array_equal(ctx, r.context)
    assert not np.array_equal(a[0].query, b[0].query)
