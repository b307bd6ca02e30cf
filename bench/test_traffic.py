"""The traffic generator: lengths come from the mix file alone."""
import json
import os

import numpy as np
import pytest

import cell as CL
import model as M
import traffic as TF

MIXES = ["azure-conv-2023", "azure-code-2023"]


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_serve_the_same_lengths(name):
    mix = TF.load_mix(name)
    n = 3 * mix.clients
    a = TF.RequestStream(mix, 11, 1000).take(n)
    b = TF.RequestStream(mix, 2**31 + 17, 1000).take(n)
    assert TF.length_multiset(a) == TF.length_multiset(b)
    # the first round (the fill) is the same multiset too
    assert (TF.length_multiset(a[:mix.clients])
            == TF.length_multiset(b[:mix.clients]))
    # ... in another order, with other token ids
    assert [len(d.prompt) for d in a] != [len(d.prompt) for d in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


@pytest.mark.parametrize("name", MIXES)
def test_every_group_admits_each_prompt_length_once(name):
    mix = TF.load_mix(name)
    levels = TF.prompt_levels(mix)
    draws = TF.RequestStream(mix, 2**31 + 5, 1000).take(2 * mix.clients)
    for g in range(0, len(draws), len(levels)):
        group = draws[g:g + len(levels)]
        assert sorted(len(d.prompt) for d in group) == levels


def test_a_knob_the_generator_does_not_honour_is_refused(tmp_path):
    with open(os.path.join(TF.HERE, "traffic", f"{MIXES[0]}.json")) as f:
        spec = json.load(f)
    spec["think_s"] = 2
    (tmp_path / "thinking.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="think_s"):
        TF.load_mix("thinking", str(tmp_path))


@pytest.mark.parametrize("name", MIXES)
def test_prompt_levels(name):
    levels = TF.prompt_levels(TF.load_mix(name))
    assert len(set(levels)) == 8
    assert all(t % 8 == 0 for t in levels)


def test_pool_holds_every_clients_longest_request():
    cfg = M.load_config("qwen1.5-0.5b.w8a8")
    positions, pages = CL.pool_pages(cfg, TF.load_mix("azure-conv-2023"))
    assert positions == 16 + 2200 + 537
    assert pages == 24 * 44 + 1
    positions, pages = CL.pool_pages(cfg, TF.load_mix("azure-code-2023"))
    assert positions == 16 + 3232 + 40
    assert pages == 16 * 52 + 1


def test_same_seed_same_requests():
    mix = TF.load_mix("azure-code-2023")
    a = TF.RequestStream(mix, 5, 49152).take(30)
    b = TF.RequestStream(mix, 5, 49152).take(30)
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
