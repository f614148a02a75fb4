"""The permute-byte count, on HLO text and on a small compiled program."""
import os
import subprocess
import sys

from bench import hlo

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TPU_LINES = """
  %collective-permute-start.7 = (u32[1,24,1024,128]{3,2,1,0:T(8,128)}, u32[1,24,1024,128]{3,2,1,0:T(8,128)}, u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%bitcast.9), channel_id=3, source_target_pairs={{0,1},{1,2},{2,3},{3,0}}
  %collective-permute-done.7 = u32[1,24,1024,128]{3,2,1,0:T(8,128)} collective-permute-done(%collective-permute-start.7)
  %collective-permute.2 = f32[1,24,1024,1]{3,2,1,0} collective-permute(%scale.1), channel_id=4, source_target_pairs={{0,3},{1,0},{2,1},{3,2}}
  %fusion.3 = f32[8]{0} fusion(%collective-permute.2), kind=kLoop
"""


def test_permute_operands_of_tpu_lines():
    ops = hlo.permute_operands(TPU_LINES)
    assert ops == [("u32", (1, 24, 1024, 128)), ("f32", (1, 24, 1024, 1))]
    assert hlo.permute_bytes(TPU_LINES) == 4 * 24 * 1024 * 128 + 4 * 24 * 1024


SCRIPT = r"""
import os, sys
sys.path.insert(0, sys.argv[1])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from bench import hlo
mesh = Mesh(np.asarray(jax.devices()[:4]), ("node",))
sh = NamedSharding(mesh, P("node"))
f = jax.jit(lambda w, s: (jnp.roll(w, 1, axis=0), jnp.roll(s, -1, axis=0)),
            in_shardings=(sh, sh), out_shardings=(sh, sh))
c = f.lower(jax.ShapeDtypeStruct((4, 16, 32), jnp.uint32),
            jax.ShapeDtypeStruct((4, 16, 1), jnp.float32)).compile()
print(hlo.permute_bytes(c.as_text()))
"""


def test_permute_bytes_of_a_compiled_ring():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SCRIPT, ROOT], env=env, check=True,
                         capture_output=True, text=True, timeout=300).stdout
    # per device: one node's 16 x 32 u32 words and 16 x 1 f32 scales
    assert int(out.strip().splitlines()[-1]) == 16 * 32 * 4 + 16 * 4
