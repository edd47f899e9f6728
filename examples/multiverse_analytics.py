"""Cross-version analytics at device scale: the intro's motivating queries
("aggregate count of protein-protein tuples with confidence > 0.9, for each
version"; "versions with a bulk delete") through the bitmap kernels.

  PYTHONPATH=src python examples/multiverse_analytics.py
"""
import numpy as np

from repro.core import generate
from repro.core import query as Q
from repro.kernels import ops


def main():
    # protein-protein-style CVD: scores in columns 2..4
    w = generate("CUR", n_versions=120, inserts=300, n_branches=12,
                 n_attrs=8, seed=4)
    print(f"CVD: {w.n_versions} versions (DAG with merges), "
          f"{w.n_records} records")

    # bitset vlists once; every query below is one kernel pass
    bm = ops.build_bitmap(w.graph.rlists(), w.n_records)
    print(f"bitset vlists: {bm.nbytes/1e6:.2f} MB vs "
          f"{w.graph.indices.nbytes/1e6:.2f} MB CSR")

    # Q1: per-version COUNT of high-confidence interactions (col2 > 900)
    conf = (w.data[:, 2] > 900).astype(np.float32)
    counts = np.asarray(ops.version_aggregate(bm, conf))[:w.n_versions]
    top = np.argsort(-counts)[:5]
    print("Q1 top versions by count(col2>900):",
          [(int(v), int(counts[v])) for v in top])

    # Q2: per-version SUM of a score column
    sums = np.asarray(ops.version_aggregate(
        bm, w.data[:, 3].astype(np.float32)))[:w.n_versions]
    print(f"Q2 sum(col3) range across versions: "
          f"[{sums.min():.0f}, {sums.max():.0f}]")

    # Q3: which versions contain a specific record (membership kernel)
    target_rid = int(w.graph.rlist(10)[0])
    mask, _ = ops.membership_scan(bm, vid=10)
    vlist_of_record = np.flatnonzero(bm[:, target_rid])  # word-level, then bits
    print(f"Q3 record r{target_rid}: member of version 10? "
          f"{bool(np.asarray(mask)[target_rid])}")

    # Q4: versions with a bulk delete (>100 records dropped vs a parent)
    parents = [list(w.vgraph.parents(v)) for v in range(w.n_versions)]
    bulk = Q.versions_with_bulk_delete(w.graph, parents, threshold=100)
    print(f"Q4 bulk-delete versions (>100 dropped): {bulk[:10].tolist()}")

    # Q5: cross-version join on the PK prefix (paper §2.2 renaming query)
    j = Q.join_versions(w.graph, w.data, 5, 50, on=0)
    print(f"Q5 join(v5, v50) on col0: {len(j)} row pairs")


if __name__ == "__main__":
    main()
