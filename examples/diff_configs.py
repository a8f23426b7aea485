"""Before/after workflow: diff the communication of two configurations.

    PYTHONPATH=src python examples/diff_configs.py

Traces the same arch x shape under two serving weight placements (FSDP-
sharded vs replicated-over-data) and prints the per-class traffic diff —
the paper's case-study loop ("change a UCX setting, compare the graphs")
as one function call on two compiled artifacts: the per-layer weight
all-gathers vanish under replication, traded for per-device memory.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import dataclasses

from repro.core import MeshSpec
from repro.core.diff import render_diff
from repro.launch import presets
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh


def main():
    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MeshSpec((2, 4), ("data", "model"))
    arch, shape = "mixtral-8x22b", "decode_32k"

    st = presets.settings_for(arch, shape)
    base = lower_cell(arch, shape, mesh=mesh, mesh_spec=spec,
                      settings=dataclasses.replace(st, serve_fsdp=True))
    opt = lower_cell(arch, shape, mesh=mesh, mesh_spec=spec,
                     settings=dataclasses.replace(st, serve_fsdp=False))
    a, b = base["trace"], opt["trace"]
    a.label, b.label = "fsdp-weights", "replicated-weights"
    print(f"per-device memory (analytic): {base['mem_model_gb']} GB -> "
          f"{opt['mem_model_gb']} GB")
    print(render_diff(a, b))
    print()
    print(render_diff(a, b, by="semantic"))


if __name__ == "__main__":
    main()
