"""Each configuration file states its model as published, changed only in
the keys it lists under ``reduced``, and BENCHMARK.json lists the same
keys; the program's registry agrees with every key the file states.

Each file maps the published keys it is held to onto its ``model``
block (``from_published``; a dotted path reaches into a nested block).
"""
import json

import pytest

from chipbench import cells, harness

BENCH = json.loads(cells.BENCHMARK.read_text())
FILES = {c["name"]: json.loads((cells.ROOT / c["file"]).read_text())
         for c in BENCH["configs"]}
DENSE = [n for n, f in FILES.items() if f["model"]["family"] == "dense"]


def _at(model, path):
    for key in path.split("."):
        model = model[key]
    return model


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_is_published_but_for_reduced(entry):
    config = FILES[entry["name"]]
    assert config["name"] == entry["name"]
    assert sorted(config["reduced"]) == sorted(entry["reduced"])
    model, published = config["model"], config["published"]
    mapped = config["from_published"]
    assert set(config["reduced"]) <= set(mapped), "a reduced key unchecked"
    for key, ours in mapped.items():
        if key in config["reduced"]:
            assert published[key] == config["reduced"][key]["from"]
            assert _at(model, ours) == config["reduced"][key]["to"]
        else:
            assert _at(model, ours) == published[key], key
    if model["family"] == "dense":
        assert model["head_dim"] * model["num_heads"] == published["hidden_size"]
        assert model["activation"] == "swiglu" and published["hidden_act"] == "silu"
    harness.make_arch(config)


@pytest.mark.parametrize("name", DENSE)
def test_dense_files_hold_the_published_keys_they_held(name):
    """The dense files map every published size they were held to before
    the map moved into them."""
    assert FILES[name]["from_published"] == {
        "hidden_size": "d_model", "intermediate_size": "d_ff",
        "num_attention_heads": "num_heads",
        "num_key_value_heads": "num_kv_heads",
        "num_hidden_layers": "num_layers", "vocab_size": "vocab_size",
        "tie_word_embeddings": "tie_embeddings"}
