"""Traffic generator: determinism per seed, the same work for every seed,
the mean rate, and the burst phases."""
import bench_fixture  # noqa: F401
import numpy as np
import pytest

from bench.lib import traffic

MIX = {"kind": "open", "shape_seed": 3, "rate_qps": 20.0,
       "phases": [{"seconds": 2, "rate_mult": 2.5},
                  {"seconds": 6, "rate_mult": 0.5}],
       "prompt": {"median": 32, "sigma": 0.6, "min": 8, "max": 128},
       "output": {"median": 48, "sigma": 0.6, "min": 8, "max": 256},
       "audio": {"sample_rate": 48000, "clip_s": [0.01, 0.02],
                 "weights": [0.5, 0.5]}}


def _key(p):
    return (p.due, tuple(p.prompt), p.max_new,
            None if p.audio is None else p.audio.tobytes())


def test_same_seed_same_requests():
    a = traffic.plan(MIX, 2**40 + 5, 80.0, 1000, 4)
    b = traffic.plan(MIX, 2**40 + 5, 80.0, 1000, 4)
    assert [_key(x) for x in a] == [_key(x) for x in b]


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.plan(MIX, 11, 80.0, 1000, 4)
    b = traffic.plan(MIX, 12, 80.0, 1000, 4)
    assert len(a) == len(b)
    for f in (lambda p: len(p.prompt), lambda p: p.max_new,
              lambda p: len(p.audio)):
        assert sorted(map(f, a)) == sorted(map(f, b))
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in b]
    assert [p.due for p in a] != [p.due for p in b]
    # gaps are reshuffled within each phase segment, so every segment
    # keeps its arrival count
    edges = [0.0]
    while edges[-1] < 80.0:
        edges.append(edges[-1] + (2.0 if len(edges) % 2 else 6.0))
    for lo, hi in zip(edges, edges[1:]):
        na = sum(lo <= p.due < hi for p in a)
        nb = sum(lo <= p.due < hi for p in b)
        assert na == nb


def test_closed_loop_clients_keep_their_sequences_for_every_seed():
    """A closed-loop window serves only the head of each client's
    sequence, so every seed deals the same sequences, to other clients."""
    mix = {"kind": "closed", "shape_seed": 5, "clients_per_slot": 2,
           "requests_per_client": 6,
           "prompt": MIX["prompt"], "output": MIX["output"]}

    def sequences(seed):
        by = {}
        for p in traffic.plan(mix, seed, 10.0, 1000, 4):
            by.setdefault(p.client, []).append((len(p.prompt), p.max_new))
        return by

    a, b = sequences(2**35 + 1), sequences(2**35 + 2)
    assert sorted(a) == sorted(b) == list(range(8))
    assert all(len(q) == 6 for q in a.values())
    assert sorted(a.values()) == sorted(b.values())
    assert a != b


def test_mean_rate_and_bursts():
    mix = dict(MIX, shape_seed=4)
    a = traffic.arrivals(mix, 800.0, 1)
    assert len(a) / 800.0 == pytest.approx(20.0, rel=0.05)
    phase = np.mod(a, 8.0) < 2.0
    assert phase.sum() / 200.0 == pytest.approx(50.0, rel=0.08)   # 2.5x
    assert (~phase).sum() / 600.0 == pytest.approx(10.0, rel=0.08)  # 0.5x
    assert np.all(np.diff(a) > 0) and a[0] >= 0 and a[-1] < 800.0


def test_lengths_are_clipped_lognormals():
    x = traffic.lengths({"median": 256, "sigma": 0.9, "min": 16,
                         "max": 2048}, np.random.default_rng(0), 20000)
    assert x.min() >= 16 and x.max() <= 2048
    assert np.median(x) == pytest.approx(256, rel=0.05)


def test_closed_loop_deals_requests_to_clients():
    mix = {"kind": "closed", "shape_seed": 1, "clients_per_slot": 2,
           "requests_per_client": 3,
           "prompt": {"median": 64, "sigma": 0.5, "min": 8, "max": 128},
           "output": {"median": 8, "sigma": 0.5, "min": 2, "max": 16}}
    p = traffic.plan(mix, 5, 10.0, 100, 4)
    assert len(p) == 24 and all(x.due is None for x in p)
    assert sorted({x.client for x in p}) == list(range(8))
