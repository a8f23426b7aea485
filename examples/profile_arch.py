"""Profile any assigned architecture x shape on a reduced host mesh and
write the interactive HTML report (the paper's visualizer artifact).

    PYTHONPATH=src python examples/profile_arch.py --arch mixtral-8x22b \
        --shape decode_32k --out /tmp/trace.html

Uses the reduced (smoke) config of the same family so it compiles in
seconds on CPU; the production 512-chip traces come from
`python -m repro.launch.dryrun --html results/html`.
"""
import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

from repro.core import MeshSpec
from repro.core.report import semantic_table, summary, to_html, top_contenders_table
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mixtral-8x22b")
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--out", default="/tmp/repro_trace.html")
    args = ap.parse_args()

    mesh = make_mesh((2, 4), ("data", "model"))
    spec = MeshSpec((2, 4), ("data", "model"))
    print(f"tracing {args.arch} x {args.shape} on a 2x4 host mesh ...")
    r = lower_cell(args.arch, args.shape, mesh=mesh, mesh_spec=spec)
    if "skipped" in r:
        print("cell skipped:", r["skipped"])
        return
    tr = r["trace"]
    print(summary(tr))
    print(top_contenders_table(tr))
    print(semantic_table(tr))
    with open(args.out, "w") as f:
        f.write(to_html(tr, spec))
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
