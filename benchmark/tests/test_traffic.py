"""The traffic generator: same histogram for every seed, contents from the seed."""

import numpy as np

from benchmark import traffic

UNIFORM = {"distribution": "uniform", "min": 384, "max": 768, "placement": "seeded"}
LOGNORMAL = {"distribution": "lognormal", "median": 160, "sigma": 0.5, "min": 32, "max": 256, "placement": "fixed"}


def test_every_seed_has_the_same_histogram_and_token_total():
    a, b = traffic.placed_lengths(UNIFORM, 32, 1), traffic.placed_lengths(UNIFORM, 32, 2)
    assert sorted(a) == sorted(b) == sorted(traffic.length_grid(UNIFORM, 32))
    assert list(a) != list(b) and a.min() >= 384 and a.max() <= 768
    assert abs(a.mean() - 576) < 1


def test_fixed_placement_ignores_the_seed_but_contents_do_not():
    a, b = traffic.placed_lengths(LOGNORMAL, 2048, 1), traffic.placed_lengths(LOGNORMAL, 2048, 2)
    assert list(a) == list(b)
    assert a.min() == 32 and a.max() == 256 and abs(np.median(a) - 160) <= 1
    rows1, rewards1 = traffic.ilql_dataset({"n_rows": 16, "row_length": LOGNORMAL}, 50257, 1)
    rows2, rewards2 = traffic.ilql_dataset({"n_rows": 16, "row_length": LOGNORMAL}, 50257, 2)
    assert [len(r) for r in rows1] == [len(r) for r in rows2]
    assert any((x != y).any() for x, y in zip(rows1, rows2)) and rewards1 != rewards2
    again, _ = traffic.ilql_dataset({"n_rows": 16, "row_length": LOGNORMAL}, 50257, 1)
    assert all((x == y).all() for x, y in zip(rows1, again))


def test_no_pad_or_eos_id_is_drawn():
    rows = traffic.ppo_prompts({"n_prompts": 8, "prompt_length": UNIFORM}, 512, 3)
    assert min(int(r.min()) for r in rows) >= 2 and max(int(r.max()) for r in rows) < 512
    assert all(0.0 <= x <= 1.0 for x in traffic.ppo_reward(rows, 512))
