"""The traffic generator: seeded, the same work for every seed, unique prompts."""
import pytest

from bench import traffic

MIXES = ("cascade", "gist")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_queries(name):
    mix = traffic.load_mix(name)
    a, b = traffic.Traffic(mix, 2**33 + 5), traffic.Traffic(mix, 2**33 + 5)
    assert [a.query().records for _ in range(3)] == [b.query().records for _ in range(3)]


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_lengths(name):
    mix = traffic.load_mix(name)

    def lengths(seed):
        q = traffic.Traffic(mix, seed).query()
        return sorted(len(r["doc"]) for r in q.records)

    assert lengths(1) == lengths(2**31 + 11)
    spec = mix["fields"]["doc"]
    assert spec["min"] <= min(lengths(3)) and max(lengths(3)) <= spec["max"]


@pytest.mark.parametrize("name", MIXES)
def test_prompts_unique_and_warm_up_disjoint(name):
    mix = traffic.load_mix(name)
    gen, warm = traffic.Traffic(mix, 7), traffic.Traffic(mix, 7, warm=True)
    ids = [r["id"] for _ in range(20) for r in gen.query().records]
    warm_ids = [r["id"] for _ in range(5) for r in warm.query().records]
    assert len(set(ids)) == len(ids)
    assert not set(ids) & set(warm_ids)
    texts = [r["doc"] for r in traffic.Traffic(mix, 7).query().records]
    assert len(set(texts)) == len(texts)
