"""Multi-host distributed backend (VERDICT r2 component 43: the DCN
half of the comm story, executable rather than spec-only).

Two OS processes join a jax.distributed runtime (gloo collectives over
TCP — the DCN stand-in), each contributing 4 host devices to one global
(dp=4, sp=2) mesh with sp confined inside a process (the ICI axis) and
dp spanning processes. The sharded batched ExtendBlock program runs
SPMD across all 8 devices and every host verifies the DAH of its blocks
against the host reference path.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # multi-host DCN backend (2 OS processes) — run with --all

WORKER = r"""
import sys
proc_id, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]

from celestia_tpu.parallel import multihost

multihost.initialize(
    f"127.0.0.1:{port}", nprocs, proc_id,
    platform="cpu", local_device_count=4,
)

import jax
import numpy as np
from jax.experimental import multihost_utils

import __graft_entry__ as graft
from celestia_tpu import da

assert jax.process_count() == nprocs, jax.process_count()
mesh = multihost.process_mesh(sp=2)
assert mesh.devices.shape == (4, 2), mesh.devices.shape
# sp must be intra-process: both devices of each sp row share a process
for row in mesh.devices:
    assert len({d.process_index for d in row}) == 1, "sp crossed DCN"

k = 4
B = 4  # dp-global batch: one block per dp row
square = graft._example_square(k)
batch = np.broadcast_to(square, (B, k, k, 512))
# every host contributes ITS slice of the dp axis
local = batch[proc_id * (B // nprocs):(proc_id + 1) * (B // nprocs)]

fn = multihost.distributed_extend_and_root(mesh, k)
global_in = multihost.shard_batch_from_host(np.ascontiguousarray(local), mesh)
out = fn(global_in)
jax.block_until_ready(out)

dahs = multihost_utils.process_allgather(out[3], tiled=True)
dahs = np.asarray(dahs).reshape(-1, 32)

expected = da.new_data_availability_header(da.extend_shares(square)).hash()
for i in range(B):
    assert dahs[i].tobytes() == expected, f"block {i} DAH mismatch"
print(f"MULTIHOST_OK proc={proc_id} dah={expected.hex()[:16]}", flush=True)
"""


def _scrubbed_env(extra=None):
    """The worker processes' env: CPU pinned, as __graft_entry__ does."""
    import __graft_entry__ as graft

    env = {k: v for k, v in os.environ.items()
           if k not in graft._SCRUB_EXACT}
    env["JAX_PLATFORMS"] = "cpu"
    # the worker runs as a script from tmp_path — scripts put their own
    # directory on sys.path, not the cwd
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env.update(extra or {})
    return env


@pytest.mark.slow
class TestMultiHost:
    def test_two_process_global_mesh_extend(self, tmp_path):
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()

        worker = tmp_path / "worker.py"
        worker.write_text(WORKER)
        procs = [
            subprocess.Popen(
                [sys.executable, str(worker), str(i), "2", str(port)],
                env=_scrubbed_env(),
                cwd="/root/repo",
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            # generous: two fresh processes each compile the sharded
            # program; under a loaded CI box this can take minutes
            out, _ = p.communicate(timeout=600)
            outs.append(out)
        for i, (p, out) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"proc {i} failed:\n{out[-3000:]}"
            assert f"MULTIHOST_OK proc={i}" in out, out[-2000:]
        # both hosts agreed on the same DAH. Parse the hex digest with a
        # REGEX rather than taking the line tail: Gloo/distributed-init
        # chatter shares the child's stdout fd and can interleave onto
        # the result line without a newline (observed flake), so
        # anything after the hex run must be ignored.
        import re

        per_proc = []
        for i, out in enumerate(outs):
            # exactly 16 hex chars (the worker prints hex()[:16]) — an
            # open-ended quantifier could absorb hex-looking chatter
            matches = re.findall(
                rf"MULTIHOST_OK proc={i} dah=([0-9a-f]{{16}})", out
            )
            assert len(matches) == 1, (i, matches, out[-500:])
            per_proc.append(matches[0])
        assert per_proc[0] == per_proc[1], per_proc
