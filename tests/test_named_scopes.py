"""The training step's device work carries stable names: the
``jax.named_scope``s of the model and the step reach every matrix
product's ``op_name`` in the compiled HLO, forward and backward, whatever
implements attention."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import params as PM
from repro.optim import adamw
from repro.train.step import make_train_step

SCOPES = ("embed", "attention", "attention_core", "mlp", "lm_head",
          "optimizer")


def _scope(op_name):
    """(innermost scope, backward?) of an HLO ``op_name``: a component
    named after a scope, bare or wrapped in ``jvp(...)``/``transpose(...)``."""
    scope = None
    for part in op_name.split("/"):
        bare = re.sub(r"^((jvp|transpose)\()+|\)+$", "", part)
        if bare in SCOPES:
            scope = bare
    return scope, "transpose(" in op_name


@functools.lru_cache(maxsize=None)
def _compiled_step(impl):
    cfg = get_config("opt-125m").reduced(num_layers=2, d_model=128,
                                         vocab_size=512)
    oc = adamw.OptConfig()
    params = jax.eval_shape(lambda k: PM.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    opt = jax.eval_shape(lambda p: adamw.init_opt_state(p, oc), params)
    batch = {k: jax.ShapeDtypeStruct((2, 128), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(cfg, oc, attn_impl=impl))
    return step.lower(params, opt, batch).compile().as_text()


def _op_names(text, opcode=None):
    """op_name of every instruction (of ``opcode`` only, if given)."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*\S+\s+([\w\-]+)\(",
                     line)
        op = re.search(r'op_name="([^"]*)"', line)
        if m and op and (opcode is None or m.group(1) == opcode):
            out.append(op.group(1))
    return out


@pytest.mark.parametrize("impl", ["chunked", "pallas"])
def test_every_product_of_the_step_is_under_a_scope(impl):
    text = _compiled_step(impl)
    dots = _op_names(text, "dot")
    assert dots
    unscoped = [op for op in dots if _scope(op)[0] is None]
    assert not unscoped, unscoped[:5]
    seen = {_scope(op) for op in _op_names(text)}
    for scope in ("attention_core", "mlp", "lm_head", "embed"):
        assert (scope, False) in seen and (scope, True) in seen, scope
    assert ("optimizer", False) in seen


def test_pallas_kernels_sit_under_attention_core():
    """Every op of the FA-2 kernels (interpret mode here: the kernels'
    bodies as loops) is an ``attention_core`` op, forward and backward."""
    text = _compiled_step("pallas")
    kernel = [op for op in _op_names(text)
              if "jit(flash_attention_bhsd)" in op]
    assert kernel
    assert all(_scope(op)[0] == "attention_core" for op in kernel)
    assert {_scope(op)[1] for op in kernel} == {False, True}
