"""The configuration files against the program's registered
architectures, and the benchmark's weight trees against the program's
parameter layout."""
import dataclasses
import json

import jax
import pytest

import dims
import weights

NAMES = ["deepseek-7b"]
# sizes a configuration may change from the registered architecture, by
# the benchmark's own configuration keys
MAY_DIFFER = {"n_layers", "rope_theta", "lora_dropout"}


@pytest.mark.parametrize("name", NAMES)
def test_widths_match_the_registered_architecture(name):
    from repro.configs import get_config
    d = dims.load(name)
    arch = get_config(d.program)
    assert (d.d_model, d.heads, d.kv_heads, d.head_dim, d.d_ff, d.vocab,
            d.experts, d.top_k, d.qk_norm) == (
        arch.d_model, arch.n_heads, arch.n_kv_heads, arch.head_dim,
        arch.d_ff, arch.vocab_size, arch.n_experts, arch.top_k, arch.qk_norm)
    run = dims.program_config(d)
    changed = {f.name for f in dataclasses.fields(arch)
               if getattr(arch, f.name) != getattr(run, f.name)}
    assert changed - {"d_head"} <= MAY_DIFFER
    assert run.head_dim == arch.head_dim


@pytest.mark.parametrize("name", NAMES)
def test_file_states_its_cuts(name):
    c = json.loads((dims.HERE / "configs" / f"{name}.json").read_text())
    assert c["source"].startswith("https://")
    assert set(c["reduced"]) == {"num_hidden_layers"}
    assert c["reduced"]["num_hidden_layers"]["run"] == c["num_hidden_layers"]
    assert c["deployment"] and c["assumed"]["lora_dropout"]


@pytest.mark.parametrize("name", NAMES)
def test_weight_trees_follow_the_program_layout(name):
    from repro.launch.train import abstract_base
    from repro.core.methods import get_method
    d = dataclasses.replace(dims.load(name), layers=2)
    cfg = dims.program_config(d)
    want = abstract_base(cfg)
    got = jax.eval_shape(lambda k: weights.make_base(k, d),
                         jax.random.PRNGKey(0))
    assert (jax.tree.structure(got) == jax.tree.structure(want))
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    ad_want = jax.eval_shape(lambda: get_method("fedlora_opt").make_adapter(
        want, cfg, jax.random.PRNGKey(0)))
    ad = jax.eval_shape(lambda k: weights.make_adapters(k, d, 1),
                        jax.random.PRNGKey(0))
    assert jax.tree.structure(ad) == jax.tree.structure(ad_want)
    for a, b in zip(jax.tree.leaves(ad), jax.tree.leaves(ad_want)):
        assert a.shape == (1,) + b.shape and a.dtype == b.dtype


def test_seeds_past_32_bits_give_distinct_keys():
    a = weights.seed_key(2 ** 31 + 5)
    b = weights.seed_key(5)
    assert not (jax.random.key_data(a) == jax.random.key_data(b)).all()
