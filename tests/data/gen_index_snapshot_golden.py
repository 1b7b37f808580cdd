"""Generate the pinned v1 index snapshot fixture.

    PYTHONPATH=src python tests/data/gen_index_snapshot_golden.py

Builds a small deterministic index (sq8 store materialized), snapshots it
through the persist format, and embeds the expected ``search_batch``
outputs (exact + sq8 two-stage) as an extra ``expected`` section —
``tests/test_snapshot_golden.py`` asserts any future build keeps loading
this v1 file AND serves bit-identical results from it.  Regenerate ONLY on
a deliberate format-version bump (and keep a reader for v1).

When a toolchain legitimately changes float rounding, refresh only the
pinned distances, keeping every index section as it is:

    PYTHONPATH=src python tests/data/gen_index_snapshot_golden.py --refresh-dists

That mode refuses to write unless the replayed ids are bit-identical.
"""
from __future__ import annotations

import os

import numpy as np


def refresh_dists(path):
    """Replay the pinned searches on the loaded fixture; rewrite only the
    ``expected`` section's distances, and only if the ids are unchanged."""
    from repro.persist.format import read_snapshot, write_snapshot
    from repro.persist.snapshot import KIND, load_index

    payload, sections = read_snapshot(path)
    exp = sections["expected"]
    idx = load_index(path)
    for tag, codec in (("exact", None), ("sq8", "sq8")):
        res = idx.search_batch(exp["queries"], k=10, eps=0.1,
                               quantized=codec)
        if not np.array_equal(np.asarray(res.ids), exp[f"{tag}_ids"]):
            raise SystemExit(f"{tag}_ids changed: not a rounding-only "
                             "change, refusing to refresh the fixture")
        new = np.asarray(res.dists)
        d = np.abs(new - exp[f"{tag}_dists"])
        print(f"{tag}_ids: bit-identical; {tag}_dists: max |diff| "
              f"{d.max():.3g}, {int((d > 0).sum())} of {d.size} differ")
        exp[f"{tag}_dists"] = new
    write_snapshot(path, KIND, sections, payload)
    print(f"wrote {path}: only the expected *_dists arrays changed")


def main():
    import sys

    path = os.path.join(os.path.dirname(__file__),
                        "index_snapshot_golden.npz")
    if "--refresh-dists" in sys.argv:
        refresh_dists(path)
        return
    from repro.core.build import DEGParams, build_deg
    from repro.persist.format import write_snapshot
    from repro.persist.snapshot import KIND, index_sections

    rng = np.random.default_rng(7)
    vecs = rng.normal(size=(120, 8)).astype(np.float32)
    idx = build_deg(vecs, DEGParams(degree=8, k_ext=16), wave_size=8,
                    refine_iterations=30)
    idx.store_for("sq8")
    queries = (vecs[:8] + 0.05 * rng.normal(size=(8, 8))).astype(np.float32)
    exact = idx.search_batch(queries, k=10, eps=0.1)
    quant = idx.search_batch(queries, k=10, eps=0.1, quantized="sq8")

    sections, payload = index_sections(idx)
    sections["expected"] = {
        "queries": queries,
        "exact_ids": np.asarray(exact.ids),
        "exact_dists": np.asarray(exact.dists),
        "sq8_ids": np.asarray(quant.ids),
        "sq8_dists": np.asarray(quant.dists),
    }
    write_snapshot(path, KIND, sections, payload)
    print(f"wrote {path}: n={idx.n}, sections={sorted(sections)}")


if __name__ == "__main__":
    main()
