"""Layouts found by name: the dense decoder's weights and costs as before,
an untied head and a head size of its own, and the norm eps passed on or
refused."""
import dataclasses
import hashlib
import os

import jax
import numpy as np
import pytest

import costs
import model as M
import traffic as TF

TD = os.path.join(os.path.dirname(os.path.abspath(__file__)), "testdata")
QWEN = M.load_config("qwen1.5-0.5b.w8a8")
TINY = M.load_config("tiny-dense", os.path.join(TD, "configs"))


def checksum(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        for part in (jax.tree_util.keystr(path), str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_qwen_weights_are_the_ones_drawn_before():
    """Qwen1.5-0.5B's draw at two layers and a 4096-token vocabulary (the
    full size is the chip's), against the checksum of the same draw by the
    harness before layouts were found by name."""
    small = dict(QWEN, num_hidden_layers=2, vocab_size=4096)
    assert checksum(M.make_weights(small, 2**33 + 7)) == (
        "1b67e8c9399d5d255f0dee702276b6ef5673d51eaeec096bbe17c75f6f02d546")


def test_qwen_costs_are_the_ones_counted_before():
    """Every function of costs.py at the Qwen cells' slots and prompt
    lengths, against the values the harness gave before layouts."""
    assert costs.linear_shapes(QWEN) == [(1024, 3072), (1024, 1024),
                                         (1024, 2816), (1024, 2816),
                                         (2816, 1024)]
    assert costs.linear_params(QWEN) == 463863808
    for B, ops, nbytes in ((24, 22265462784.0, 508868096.0),
                           (16, 14843641856.0, 494413312.0)):
        calls = costs.w8a8_step_calls(QWEN, B)
        assert len(calls) == 121
        assert sum(o for o, _ in calls) == ops
        assert sum(b for _, b in calls) == nbytes
    assert costs.flash_decode_step(QWEN, 24, 27648) == (2717908992.0,
                                                        2720268288.0)
    assert costs.flash_decode_step(QWEN, 16, 26992) == (2653421568.0,
                                                        2654994432.0)
    assert costs.decode_flops(QWEN, 24, 27648) == 24983371776.0
    assert costs.decode_flops(QWEN, 16, 26992) == 17497063424.0
    prefill = {
        "azure-conv-2023": [308364443648.0, 426992205824.0, 526316208128.0,
                            627678642176.0, 742694322176.0, 890002866176.0,
                            1109035188224.0, 1598213193728.0],
        "azure-code-2023": [459873714176.0, 644770562048.0, 795272413184.0,
                            956064333824.0, 1133875429376.0,
                            1368419729408.0, 1719393714176.0,
                            2511717269504.0]}
    for mix, want in prefill.items():
        levels = TF.prompt_levels(TF.load_mix(mix))
        assert [costs.prefill_flops(QWEN, T, 16) for T in levels] == want


def test_untied_head_and_head_size():
    """An untied head is drawn from a key of its own, so every other leaf
    is the tied draw; a stated head_dim sets the attention's widths."""
    tied = M.make_weights(TINY, 5)
    untied = M.make_weights(dict(TINY, tie_word_embeddings=False), 5)
    assert untied["head"]["w"].shape == (128, 512)
    head = untied.pop("head")
    assert checksum(untied) == checksum(tied)
    assert not np.array_equal(np.asarray(head["w"]).T,
                              np.asarray(tied["embed"]["w"]))

    wide = dict(TINY, head_dim=64)
    attn = M.make_weights(wide, 5)["layers"]["attn"]
    assert attn["wqkv"].shape == (2, 128, (4 + 2 * 2) * 64)
    assert attn["wo"].shape == (2, 4 * 64, 128)
    assert M.program_config(wide).head_dim == 64
    assert costs.linear_shapes(wide)[:2] == [(128, 512), (256, 128)]


def test_norm_eps_is_passed_on_or_refused():
    """The program gets the configuration's RMSNorm eps where its
    ModelConfig takes one; where it does not, any eps but the one it fixes
    is refused rather than served differently."""
    from repro.configs.base import ModelConfig
    other = dict(TINY, rms_norm_eps=1e-5)
    if "norm_eps" in {f.name for f in dataclasses.fields(ModelConfig)}:
        assert M.program_config(other).norm_eps == 1e-5
    else:
        with pytest.raises(ValueError, match="rms_norm_eps"):
            M.program_config(other)
    assert M.program_config(TINY).n_layers == 2


def test_unknown_layout_is_refused():
    with pytest.raises(FileNotFoundError, match="no_such_layout"):
        M.make_weights(dict(TINY, layout="no_such_layout"), 1)
