"""The reference against the program at a tiny size on the CPU: both
parameter translations (training tree, int8 serving tree) reproduce the
program's own forward pass."""

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from deepspeed_tpu.models import get_model

KW = dict(num_heads=4, eps=1e-5, activation="gelu_tanh", position_offset=0)


def test_reference_matches_the_program_float32():
    model = get_model("tiny-gpt2", dtype=jnp.float32, scan_layers=False)
    params = model.init_params(jax.random.key(1))
    ids = jax.random.randint(jax.random.key(2), (2, 48), 0, 256)
    want = model.apply(params, ids)
    got = reference.forward(reference.from_train_tree(params), ids, **KW)
    assert float(reference.logits_error(got, want)) < 1e-5
    loss = model.loss(params, {"input_ids": ids}, None)
    assert abs(float(reference.loss(reference.from_train_tree(params), ids, **KW)) - float(loss)) < 1e-5


def test_relu_variant_differs_from_gelu():
    model = get_model("tiny-gpt2", dtype=jnp.float32, scan_layers=False, activation="relu")
    params = model.init_params(jax.random.key(1))
    ids = jax.random.randint(jax.random.key(2), (1, 16), 0, 256)
    tree = reference.from_train_tree(params)
    relu = reference.forward(tree, ids, **dict(KW, activation="relu"))
    assert float(reference.logits_error(relu, model.apply(params, ids))) < 1e-5
    assert float(reference.logits_error(reference.forward(tree, ids, **KW), relu)) > 1e-3


def test_int8_tree_dequantises_to_what_the_program_multiplies():
    fp = get_model("tiny-gpt2", dtype=jnp.float32, scan_layers=False)
    q = get_model("tiny-gpt2", dtype=jnp.float32, scan_layers=False, int8_weights=True,
                  int8_fused_qkv=True)
    qparams = q.quantize_params(jax.tree_util.tree_map(np.asarray, fp.init_params(jax.random.key(1))))
    ids = jax.random.randint(jax.random.key(2), (1, 32), 0, 256)
    want = q.apply(qparams, ids)
    got = reference.forward(reference.from_int8_tree(qparams, 256), ids, **KW)
    assert float(reference.logits_error(got, want)) < 1e-4
