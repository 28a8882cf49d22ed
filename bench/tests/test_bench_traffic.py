"""The generators: same seed same inputs, sizes on the mix's grid, every
seed the same work in another order, and mixes found by name alone."""
import json
import shutil

import numpy as np
import pytest

from bench_fixtures import FIXTURES, ROOT, fixture_cell

from bench import harness, traffic

MIXES = ["code-long", "chat-short-burst"]


def mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = traffic.serve_requests(mix(name), 49152, 2**31 + 5)
    b = traffic.serve_requests(mix(name), 49152, 2**31 + 5)
    assert len(a) == len(b) == mix(name)["requests"]
    for (ra, ta, oa, da), (rb, tb, ob, db) in zip(a, b):
        assert (ra, oa, da) == (rb, ob, db) and np.array_equal(ta, tb)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_on_the_grid_and_in_bounds(name):
    tr = mix(name)
    grid = set(traffic.length_grid(tr["prompt"]))
    for _, toks, out, _ in traffic.serve_requests(tr, 49152, 7):
        assert len(toks) in grid
        assert tr["output"]["min"] <= out <= tr["output"]["max"]
        assert toks.min() >= 0 and toks.max() < 49152
    if name == "code-long":  # prompt + output never wraps the 4096 window
        assert max(grid) + tr["output"]["max"] - 1 < 4096


@pytest.mark.parametrize("name", MIXES)
def test_seeds_permute_the_same_work(name):
    tr = mix(name)
    a = traffic.serve_requests(tr, 49152, 1)
    b = traffic.serve_requests(tr, 49152, 2**32 + 3)
    block = tr["block"]
    for s in range(0, len(a) - block + 1, block):
        sa = sorted((len(t), o) for _, t, o, _ in a[s:s + block])
        sb = sorted((len(t), o) for _, t, o, _ in b[s:s + block])
        assert sa == sb
    assert [d for *_, d in a] == [d for *_, d in b]
    assert any(not np.array_equal(x[1], y[1]) for x, y in zip(a, b))


def test_bursty_arrivals_follow_the_rate():
    tr = mix("chat-short-burst")
    due = np.array([d for *_, d in traffic.serve_requests(tr, 100, 3)])
    assert np.all(np.diff(due) > 0)
    arr = tr["arrivals"]
    period = arr["burst"]["period_s"]
    hi = np.sum((due < 40) & ((due // period) % 2 == 0))
    lo = np.sum((due < 40) & ((due // period) % 2 == 1))
    assert hi > 1.5 * lo  # 1.5x against 0.5x of the mean rate


def test_fl_clients_case3_split():
    cfg = json.loads((ROOT / "bench/configs/fedveca-cnn-cifar10.json")
                     .read_text())
    tr = json.loads((ROOT / "bench/traffic/case3-c5.json").read_text())
    assert (tr["train_samples"], tr["test_samples"]) == (50000, 10000)
    # the committed mix's split at a tenth of its samples, to spare memory
    tr = dict(tr, train_samples=5000, test_samples=1000)
    clients, test = traffic.fl_clients(cfg, tr, 2**31 + 11)
    again, _ = traffic.fl_clients(cfg, tr, 2**31 + 11)
    assert len(clients) == 5
    assert sum(len(y) for _, y in clients) == tr["train_samples"]
    assert [len(y) for _, y in clients] == [834, 833, 833, 1500, 1000]
    assert all(np.array_equal(a[0], b[0]) for a, b in zip(clients, again))
    # Case 3: the last two clients each hold labels of the second half only
    for x, y in clients[3:]:
        assert set(np.unique(y)) <= set(range(5, 10))
    assert x.shape[1:] == (32, 32, 3) and len(test[1]) == tr["test_samples"]
    # every seed gives the same client sizes (one round-program shape)
    other, _ = traffic.fl_clients(cfg, tr, 3)
    assert [len(y) for _, y in other] == [len(y) for _, y in clients]
    assert not np.array_equal(other[0][0], clients[0][0])


def test_derive_takes_any_whole_number():
    for seed in (0, 2**31 - 1, 2**31 + 17, 2**40):
        s = traffic.derive(seed, "params")
        assert 0 <= s < 2**31 and s == traffic.derive(seed, "params")
    assert traffic.derive(5, "a") != traffic.derive(5, "b")


def test_a_new_mix_is_found_by_its_name_alone(tmp_path):
    """A mix that is in no manifest loads from a new file and a new
    manifest entry; no harness file changes."""
    base = tmp_path / "bench"
    shutil.copytree(FIXTURES, base)
    new = dict(mix("chat-short-burst"), requests=12, block=4)
    new["prompt"] = dict(new["prompt"], max=512)
    (base / "traffic" / "chat-new.json").write_text(json.dumps(new))
    manifest = json.loads((base / "BENCHMARK.json").read_text())
    manifest["workloads"].append({"name": "decoder-tiny.chat-new",
                                  "config": "decoder-tiny",
                                  "traffic": "chat-new", "chips": 1,
                                  "why": "a mix added as data alone"})
    cell = harness.load_cell("decoder-tiny.chat-new", manifest, base=base)
    assert cell.traffic == new and cell.kind().__name__.endswith("paged_serve")
    reqs = traffic.serve_requests(cell.traffic, 256, 9)
    assert len(reqs) == 12
    assert {len(t) for _, t, _, _ in reqs} <= set(
        traffic.length_grid(new["prompt"]))
    assert fixture_cell("decoder-tiny.chat-tiny").traffic != new
