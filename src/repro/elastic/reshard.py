"""Mesh-shrink geometry + state movement for elastic recovery.

Pure helpers under :class:`repro.elastic.ElasticMeshExecutor`:

* :func:`shrink_degree` — the DP degree a survivor set can continue at.
  The new degree must divide the ORIGINAL degree: the executor's bucket
  layout is padded to a multiple of the construction-time DP degree,
  so any divisor still tiles every bucket and the compressed sync's
  chunk math holds without re-laying-out gradients;
* :func:`survivor_submesh` — the ``(data, model)`` submesh over the kept
  physical data rows of the full mesh;
* :func:`reshard_tree` — move a pytree onto another mesh's shardings
  (``jax.device_put`` resharding transfer; bit-transparent round trip,
  proven in ``tests/test_elastic.py``);
* :func:`remap_ef_rows` — EF residuals are the one piece of state whose
  GLOBAL shape depends on the DP degree (``err1[b]`` is ``dp * B`` flat,
  one ``B``-slice per data row). Each slice follows its physical device
  row across mesh shapes; rows (re)joining the mesh start at zero
  residual (their untransmitted signal belonged to a retired trajectory).
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["shrink_degree", "survivor_submesh", "reshard_tree",
           "remap_ef_rows"]


def shrink_degree(full_degree: int, n_survivors: int) -> int:
    """Largest divisor of ``full_degree`` that is <= ``n_survivors``
    (0 when no positive degree fits — nothing survived)."""
    best = 0
    for d in range(1, min(int(full_degree), int(n_survivors)) + 1):
        if full_degree % d == 0:
            best = d
    return best


def survivor_submesh(full_mesh: jax.sharding.Mesh,
                     rows) -> jax.sharding.Mesh:
    """Submesh over the given physical ``data`` rows of the full mesh
    (every ``model`` column of each kept row rides along)."""
    idx = np.asarray(rows, dtype=np.int64)
    if idx.size == 0:
        raise ValueError("survivor submesh needs at least one data row")
    return jax.sharding.Mesh(np.asarray(full_mesh.devices)[idx],
                             full_mesh.axis_names)


def reshard_tree(tree, shardings):
    """Place ``tree`` under ``shardings`` (a matching pytree of
    :class:`~jax.sharding.NamedSharding`), moving data across meshes.
    Values are preserved bit-for-bit — only placement changes."""
    return jax.device_put(tree, shardings)


def remap_ef_rows(ef: dict, bucket_sizes, old_rows, new_rows) -> dict:
    """Re-slot ``err1`` device-row slices from ``old_rows`` (physical
    data-row ids backing each logical row of the source layout) to
    ``new_rows`` (ditto, target layout). ``err2`` is chunk-owner state
    with a dp-independent global shape and passes through unchanged."""
    old_pos = {int(p): i for i, p in enumerate(old_rows)}
    err1 = []
    for b, size in enumerate(bucket_sizes):
        buf = np.asarray(ef["err1"][b]).reshape(len(old_pos), size)
        out = np.zeros((len(new_rows), size), np.float32)
        for i, p in enumerate(new_rows):
            j = old_pos.get(int(p))
            if j is not None:
                out[i] = buf[j]
        err1.append(out.reshape(-1))
    return {"err1": tuple(err1),
            "err2": tuple(np.asarray(e) for e in ef["err2"])}
