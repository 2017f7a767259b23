"""ceph_tpu — a TPU-native re-implementation of Ceph's (charlewn/ceph v12.0.0)
capabilities, built from scratch on JAX/XLA/Pallas.

Layer map (mirrors reference SURVEY.md §1, re-designed TPU-first):

- :mod:`ceph_tpu.ops`      — device math: GF(2^w) arithmetic, RS/Cauchy coding
  matrices, batched encode/decode kernels (JAX + Pallas), CRUSH placement
  vectorized over objects, crc32c / rjenkins hashes.
- :mod:`ceph_tpu.models`   — the codec "model families": ErasureCodeInterface
  equivalent, plugin registry, jerasure / isa / lrc / shec / clay-style codecs.
- :mod:`ceph_tpu.parallel` — device mesh, shardings, distributed encode /
  reconstruct over ICI collectives (all_gather/psum/ppermute), multi-host.
- :mod:`ceph_tpu.rados`    — the distributed object-store slice: buffers,
  messenger, object store, OSD map, monitor, OSD daemon, EC backend, client.
- :mod:`ceph_tpu.utils`    — config, perf counters, admin socket, logging.
- :mod:`ceph_tpu.tools`    — benchmark harness (ceph_erasure_code_benchmark
  equivalent), crushtool equivalent, CLI.

Reference parity citations use ``reference:<path>:<line>`` for
/root/reference (charlewn/ceph).
"""

__version__ = "0.1.0"
