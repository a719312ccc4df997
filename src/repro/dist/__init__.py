"""Distribution substrate: the software analog of BARISTA's scale-up story.

The paper scales a sparse accelerator to 32K MACs by (a) hierarchical
buffering with a few wide shared buffers, (b) telescoping request-combining
to cut on-chip bandwidth, (c) colored output buffers so a node never stalls
on its siblings, and (d) dynamic round-robin load balancing. On a JAX mesh
the same four ideas become:

* :mod:`repro.dist.partitioning`      — tree-structured PartitionSpecs
  (which tensor dims live on which mesh axes; the buffer hierarchy).
* :mod:`repro.dist.collective_matmul` — overlap-friendly all-gather /
  reduce-scatter matmuls under ``shard_map`` (the snarfing reuse pattern).
* :mod:`repro.dist.compression`       — hierarchical two-stage psum
  (telescoping request-combining applied to gradient reduction).
* :mod:`repro.dist.act_sharding`      — sequence-parallel residual
  constraints (colored output buffers: proceed without waiting).
* :mod:`repro.dist.elastic`           — mesh planning, straggler detection
  and failure simulation (Section 3.4 dynamic load balancing at host
  granularity).

See ARCHITECTURE.md for the full paper-mechanism -> module map.
"""

__all__ = [
    "act_sharding",
    "collective_matmul",
    "compression",
    "elastic",
    "partitioning",
]
