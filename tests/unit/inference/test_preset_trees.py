"""What every registered preset builds, held still: ONE table of parameter
trees for all of ``available_models()``, and beside it the older guards of
logits, cache trees and declared kinds, as the PRs that took them left them.

A change to shared model code (``Block``, ``Attention``, ``MLP``, ``Mamba2``,
``cache_spec``) that moves a preset's tree moves its step programs, its
compile-cache entries and its checkpoints: it shows here, by name. A PR that
adds a preset adds a line to ``TREES``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chipbench
from deepspeed_tpu.models import available_models, get_model

from ._serving import digest

# preset -> (digest, leaves) of its parameter tree (paths, shapes, dtypes), by
# ``_serving.digest`` on ``jax.eval_shape(model.init_params, key)``; taken on
# the commits that added each preset, the last eight on a23096b (PR 57)
TREES = {
    "falcon-h1-34b-instruct": ("d472f577d7231ad5", 1227), "gpt2-125m": ("6d07fb2736454732", 20),
    "gpt2-large": ("eecb65f1ac785bed", 20), "gpt2-medium": ("849d0b37ca49721a", 20),
    "gpt2-xl": ("314611c3dbf7dfc6", 20), "k-exaone-236b-a23b": ("abbc9696e9c2ef70", 786),
    "lfm2-8b-a1b": ("cbfe333c3a226838", 256),
    # (whole it raises by design: the cut the benchmark serves, its config's overrides)
    "ling-3.0-flash": ("221d0746e5e170f3", 140), "llama2-7b": ("700e0b920ed6e38b", 12),
    "llama3-70b": ("a69a8630b0d72197", 12), "llama3-8b": ("e2bb5b2b306a8848", 12),
    "mistral-small-4-119b": ("9f1f4c1b70b5955c", 19), "mixtral-8x7b": ("a0664719c25ea1b2", 13),
    "nemotron-3-nano-30b-a3b": ("db2eebbf0b0d219a", 401),
    "olmo-hybrid-7b": ("7bf46abc96dcf40d", 475), "opt-125m": ("6146cd9c77b7a716", 20),
    "opt-66b": ("ba9a7bbc96259f34", 20), "phi-4-mini-flash-reasoning": ("6ca2df6e33827984", 502),
    "tiny": ("2e710ce0485acc07", 11), "tiny-exaone-moe": ("8bb711e3146473b6", 98),
    "tiny-falcon-h1": ("3b349aeaa6396d65", 71), "tiny-gpt2": ("6ae0caca0a306fc5", 20),
    "tiny-hybrid": ("fe34f5c3f89eebab", 62), "tiny-lfm2-moe": ("c723c56f5523fbc8", 61),
    "tiny-ling": ("f9f26e3937287259", 135), "tiny-mla-moe": ("585ee1651c3e6625", 19),
    "tiny-moe": ("68682b378434514e", 12), "tiny-nemotron-h": ("7047c47155d9e362", 59),
    "tiny-sambay": ("a16812365d3d6eca", 136),
}
PARAMETERS = {"olmo-hybrid-7b": 7430870688, "mistral-small-4-119b": 118972780544}
MULTIPLIED = {"falcon-h1-34b-instruct", "tiny-falcon-h1"}  # the published constants act


def _model(name):
    if name != "ling-3.0-flash":
        return get_model(name)
    with open(os.path.join(os.path.dirname(chipbench.__file__), "configs", name + ".json")) as f:
        return get_model(name, **json.load(f)["overrides"])


@pytest.mark.parametrize("name", sorted(TREES))
def test_the_preset_builds_the_tree_it_built(name):
    model = _model(name)
    assert digest(jax.eval_shape(model.init_params, jax.random.key(0))) == TREES[name]
    assert model.cfg.has_multipliers is (name in MULTIPLIED)
    assert PARAMETERS.get(name) in (None, model.cfg.num_params())


def test_no_preset_goes_unguarded():
    assert set(TREES) == set(available_models())


# ------------------------------------------------------------------ logits
# six logits of the last position and the mean magnitude on seeded weights, of
# the twins of cells 7, 5 and 9, taken on 6060c84 (the parent of PR 56)
TWIN_LOGITS = {
    "tiny-nemotron-h": ([-0.8989415, -0.3812234, 0.102492, 1.1450336, -1.2561585, 0.4224195],
                        0.7843328),
    "tiny-hybrid": ([-1.7705975, -0.6584616, 1.7142107, -1.0486724, 0.9665461, -0.4132772],
                    0.7986161),
    "tiny-lfm2-moe": ([-0.2671085, -0.1364899, -0.3864794, -0.1567951, -0.178684, -0.7329741],
                      0.2559913),
}


@pytest.mark.slow  # ~13 s a twin; tier-1 holds each twin's logits to its own reference already
@pytest.mark.parametrize("name", sorted(TWIN_LOGITS))
def test_the_twins_give_the_logits_they_gave(name):
    logits, magnitude = TWIN_LOGITS[name]
    model = get_model(name, dtype=jnp.float32)
    params = model.init_params(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 24), 0, model.cfg.vocab_size)
    out = model.apply(params, ids)
    out = out[0] if isinstance(out, tuple) else out
    np.testing.assert_allclose(out[1, -1, :6], logits, atol=2e-6)
    np.testing.assert_allclose(jnp.mean(jnp.abs(out)), magnitude, atol=2e-6)


# the tiny twins' logits (sum, sum of magnitudes) on seeded weights, as the
# parent of PR 54 built them
LOGITS_BEFORE = {"tiny-hybrid": (845.377414025158, 28211.244966304577),
                 "tiny-mla-moe": (-210.4655717877904, 28185.74727749292)}


@pytest.mark.parametrize("name", sorted(LOGITS_BEFORE))
def test_the_older_models_give_the_logits_they_gave(name):
    model = get_model(name, dtype=jnp.float32)
    params = model.init_params(jax.random.key(0))
    ids = jax.random.randint(jax.random.key(1), (2, 70), 0, 256)
    out = np.asarray(model.apply(params, ids), np.float64)
    np.testing.assert_allclose((out.sum(), np.abs(out).sum()), LOGITS_BEFORE[name], rtol=1e-9)


# ------------------------------------------------- cache trees and declared kinds
@pytest.mark.parametrize("name, overrides, params, pool, kinds", [
    ("gpt2-large", {}, ("eecb65f1ac785bed", 20), ("2ace4ad8ee7b3d07", 1), ("7efe6c18a76c403f", 1)),
    ("gpt2-large", {"scan_layers": False}, ("9c4584445a884b9c", 580), ("253ab50641bbfc93", 36),
     ("70973dd72aba3407", 36)),
    ("llama2-7b", {"scan_layers": False}, ("413338bc326a560b", 291), ("e55ad93897f78f38", 64),
     ("7d581e97c6a287c5", 64)),
    # PR 55: the latent leaves rest position-last, (2, 1, 320, 64), declared
    # "columns" (30bae00ac1380acc / 70973dd72aba3407 while they were rows of
    # 320; tiny-mla-moe's 3b84258aa35bd04e / 7efe6c18a76c403f likewise)
    ("mistral-small-4-119b", {"scan_layers": False}, ("d378c3c33fb6535c", 579),
     ("2b3563502a59eee8", 36), ("5bb7fe90c6ddf5e9", 36)),
    # PR 42: its state leaves rest two heads a lane row, (2, 15, 96, 384)
    # (1c5f151820235ef8 while they were (2, 30, 96, 192))
    ("olmo-hybrid-7b", {}, ("7bf46abc96dcf40d", 475), ("df45013d6f63db31", 64),
     ("72fa486ac50a3d4b", 64)),
    ("tiny-hybrid", {}, ("fe34f5c3f89eebab", 62), ("19da4dfe885cea0f", 8), ("2cb26fe38a1746cd", 8)),
    ("tiny-mla-moe", {}, ("585ee1651c3e6625", 19), ("52eea5a78f13e3c5", 1), ("d70a613fd25d4c61", 1)),
])
def test_models_without_new_layer_types_build_what_they_built(name, overrides, params, pool, kinds):
    """The parameter tree, the cache tree and the declared kinds (paths,
    shapes, dtypes; digests taken at the parent commit a87638f with this very
    function): models without the new kinds build what they built, so their
    step programs are the parent's and its compile-cache entries are hit."""
    model = get_model(name, **overrides)
    assert digest(jax.eval_shape(model.init_params, jax.random.key(0))) == params
    assert digest(jax.eval_shape(lambda: model.init_cache(2, 64))) == pool
    assert digest(model.cache_kinds()) == kinds
