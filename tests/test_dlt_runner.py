"""DLT chain runner correctness: the shard_map+ppermute chain execution of an
LP plan computes the same loss as a plain single-device pass over the same
samples.  Needs >1 device, so the multi-device parts run in a subprocess with
forced host devices (smoke tests elsewhere must keep seeing 1 device).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from repro.config import ShardingPolicy, TrainConfig, get_arch, smoke_variant
from repro.core.planner import BatchSpec, LinkSpec, Planner, StageSpec
from repro.data import batch_load_spec, make_batch
from repro.models import init_params, loss_fn
from repro.runtime import make_train_state
from repro.runtime.dlt_runner import make_dlt_train_step, stage_batches
from repro.launch.mesh import make_chain_mesh

cfg = smoke_variant(get_arch("llama3.2-3b"))
policy = ShardingPolicy(attn_chunk=16)
tcfg = TrainConfig(lr=1e-3, warmup_steps=0, total_steps=10)
B, S, m = 8, 32, 4

load = batch_load_spec(cfg, B, S)
speed = load.flops_per_sample * B / 0.05
stages = [StageSpec(f"s{i}", speed / (1 + 0.25 * i)) for i in range(m)]
links = [LinkSpec(load.bytes_per_sample * B / 0.01, 1e-4)] * (m - 1)
plan = Planner(stages, links).plan([load, load], q=2)

batches = [make_batch(cfg, B, S, step=i) for i in range(2)]
toks, labs, counts = stage_batches(plan, batches, m)
assert counts.sum() == 2 * B, counts

params = init_params(cfg, policy, seed=0, dtype=jnp.float32)
state = make_train_state(params, tcfg)
mesh = make_chain_mesh(m)
step = make_dlt_train_step(cfg, policy, tcfg, mesh, n_cells=len(plan.cells))
state2, metrics = step(state, jnp.asarray(toks), jnp.asarray(labs), jnp.asarray(counts))
chain_loss = float(metrics["loss"])

# single-device reference: mean token loss over the SAME samples
ref_num, ref_den = 0.0, 0.0
for b in batches:
    l, _ = loss_fn(params, cfg, policy, {k: jnp.asarray(v) for k, v in b.items()})
    ref_num += float(l) * B
    ref_den += B
ref_loss = ref_num / ref_den
print("chain", chain_loss, "ref", ref_loss)
assert abs(chain_loss - ref_loss) < 2e-4, (chain_loss, ref_loss)

# second step must change params (gradients flowed through the chain)
d0 = jax.tree.leaves(state.params)[0]
d1 = jax.tree.leaves(state2.params)[0]
assert not np.allclose(np.asarray(d0), np.asarray(d1))
print("OK")
"""


@pytest.mark.slow
def test_chain_loss_matches_single_device():
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), capture_output=True, text=True,
        timeout=600)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr[-2000:]}"
    assert "OK" in r.stdout


def test_stage_batches_partitions_each_load():
    from repro.config import get_arch, smoke_variant
    from repro.core.planner import BatchSpec, LinkSpec, Planner, StageSpec
    from repro.data import make_batch
    from repro.runtime.dlt_runner import stage_batches

    cfg = smoke_variant(get_arch("llama3.2-3b"))
    B, S, m = 8, 16, 3
    stages = [StageSpec(f"s{i}", 1e9) for i in range(m)]
    links = [LinkSpec(1e8, 0.0)] * (m - 1)
    plan = Planner(stages, links).plan(
        [BatchSpec(B, 64.0, 1e6), BatchSpec(B, 64.0, 1e6)], q=2)
    batches = [make_batch(cfg, B, S, step=i) for i in range(2)]
    toks, labs, counts = stage_batches(plan, batches, m)
    assert toks.shape[0] == len(plan.cells)
    assert counts.shape == (len(plan.cells), m)
    # each load's counts across its cells sum to the full batch
    for n in range(2):
        tot = sum(int(counts[t].sum()) for t, (ln, _) in enumerate(plan.cells) if ln == n)
        assert tot == B
