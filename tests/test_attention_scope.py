"""The attention core is traced under its own ``jax.named_scope``: the
compiled gradient of a reduced model's loss names it in the ``op_name`` of
the forward and the backward ops alike, on the dense path and on the
chunked one, and leaves the projections outside it."""
import re

import jax
import pytest

from repro.configs import get_config
from repro.models import attention as attn
from repro.models.api import build_model, make_batch


def grad_op_names(arch_name, seq):
    cfg = get_config(arch_name).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    batch = make_batch(cfg, jax.random.key(1), batch=1, seq=seq)
    grad = jax.jit(jax.grad(lambda p, b: model.loss(p, b, remat=True)[0]))
    text = grad.lower(params, batch).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


@pytest.mark.parametrize("arch_name", ["granite-3-2b", "deepseek-v2-lite-16b"])
@pytest.mark.parametrize("chunked", [False, True])
def test_attention_core_is_named_in_forward_and_backward(arch_name, chunked):
    seq = attn.FLASH_THRESHOLD if chunked else 64
    names = grad_op_names(arch_name, seq)
    under = [n for n in names if "attention" in n.split("/")]
    assert [n for n in under if "transpose(" not in n], "no forward op under the scope"
    assert [n for n in under if "transpose(" in n], "no backward op under the scope"
    # the chunked path's scan over key/value chunks runs inside the scope
    inner = [n.split("/")[n.split("/").index("attention") + 1:] for n in under]
    assert any("while" in parts for parts in inner) == chunked
    # the projections stay outside: some dot_general of the model is not under it
    assert [n for n in names if n.endswith("dot_general") and n not in under]
