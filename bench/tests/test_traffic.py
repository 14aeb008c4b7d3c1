import json

import pytest

from bench import traffic
from bench.run import BENCH

MIXES = ["cold"]


def load(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_stream(name):
    seed = 2 ** 31 + 977                       # beyond 32 signed bits
    assert traffic.draw(load(name), seed) == traffic.draw(load(name), seed)
    assert traffic.draw(load(name), seed) != traffic.draw(load(name), seed + 1)


@pytest.mark.parametrize("name", MIXES)
def test_values_unique_and_outside_warmup(name):
    mix = load(name)
    stream = traffic.draw(mix, 123)
    assert len(stream) == mix["max_requests"]
    for field in mix["vary"]:
        vals = [f[field] for f in stream]
        assert len(set(vals)) == len(vals)
        assert traffic.warmup_fields(mix)[field] not in vals


def test_every_seed_draws_from_one_set():
    mix = load("cold")
    a = {f["spike_density"] for f in traffic.draw({**mix, "max_requests": 1000}, 1)}
    b = {f["spike_density"] for f in traffic.draw({**mix, "max_requests": 1000}, 2)}
    assert a == b


def test_pool_too_small_is_an_error():
    mix = {**load("cold"), "max_requests": 1001}
    with pytest.raises(ValueError):
        traffic.draw(mix, 0)
