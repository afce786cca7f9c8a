"""The architecture check (``harness.make_arch``): every key of a
configuration's ``model`` block is held against the program's attribute
of that name, an object field by field against the block it holds; a key
the program lacks, and a mechanism the program has that the file does
not state, are refused."""
import copy
import json

import pytest

from chipbench import cells, harness
from chipbench.tests import moe

DENSE = {n: json.loads((cells.HERE / "configs" / f"{n}.json").read_text())
         for n in ("ds7b-2l", "yi9b-2l")}
# the keys the dense check compared before it read them from the file
DENSE_KEYS = ("family", "num_layers", "d_model", "num_heads", "num_kv_heads",
              "head_dim", "d_ff", "vocab_size", "activation", "norm_eps",
              "rope_theta", "tie_embeddings", "qkv_bias", "sliding_window",
              "compute_dtype")


def _changed(value):
    if isinstance(value, bool):
        return not value
    if isinstance(value, (int, float)):
        return value + 1
    return value + "x"


def _refused(config, *words):
    with pytest.raises(ValueError) as e:
        harness.make_arch(config)
    for w in words:
        assert w in str(e.value), str(e.value)


@pytest.mark.parametrize("name", sorted(DENSE))
@pytest.mark.parametrize("key", DENSE_KEYS)
def test_dense_file_departing_in_any_key_is_refused(name, key):
    config = copy.deepcopy(DENSE[name])
    config["model"][key] = _changed(config["model"][key])
    _refused(config, f"'{key}'")


def test_nested_block_is_held_field_by_field():
    arch = harness.make_arch(moe.config())
    assert arch.moe.num_experts == 4 and arch.moe.expert_d_ff == 32
    assert arch.moe.capacity_factor == 1.25


def test_wrong_expert_count_is_refused():
    config = moe.config()
    config["model"]["moe"]["num_experts"] = 8
    _refused(config, "'moe.num_experts': (4, 8)")


@pytest.mark.parametrize("where", ["file", "overrides"])
def test_program_mechanism_the_file_omits_is_refused(where):
    """An MoE program under a file that states no ``moe`` block, and a
    dense file whose program gains a switched-on mechanism."""
    config = moe.config()
    del config["model"]["moe"]
    _refused(config, "'moe'", "not stated")
    dense = copy.deepcopy(DENSE["ds7b-2l"])
    dense["overrides"]["cross_attn_every"] = 2
    if where == "file":
        dense["model"]["cross_attn_every"] = 2
        assert harness.make_arch(dense).cross_attn_every == 2
    else:
        _refused(dense, "'cross_attn_every'", "not stated")


@pytest.mark.parametrize("block,key", [(None, "kv_lora_rank"),
                                       ("moe", "n_shared_experts")])
def test_key_the_program_lacks_is_refused(block, key):
    config = moe.config()
    (config["model"][block] if block else config["model"])[key] = 2
    _refused(config, "no such field", key)


def test_block_the_program_lacks_is_refused():
    """A dense program under a file that states an expert block, and an
    override of a block the program does not hold."""
    config = copy.deepcopy(DENSE["yi9b-2l"])
    config["model"]["moe"] = dict(moe.EXPERTS)
    _refused(config, "'moe': (None")
    config = copy.deepcopy(DENSE["yi9b-2l"])
    config["overrides"]["moe"] = {"num_experts": 8}
    _refused(config, "no block to change")
