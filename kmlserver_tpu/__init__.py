"""kmlserver_tpu — a TPU-native rebuild of `diogoneiss/kubernetes-machine-learning-server`.

The reference system (see SURVEY.md at the repo root) is a Kubernetes-deployed
playlist-recommendation stack: a batch FP-Growth association-rule-mining job
(reference: machine-learning/main.py) and an online recommendation REST service
(reference: rest_api/app/main.py) that exchange pickled artifacts through a
shared ReadWriteMany PVC, with freshness signaled by a polled token file.

This package re-implements every component TPU-first:

- ``ops/``      — the compute kernels (JAX/XLA, Pallas): one-hot / bit-packed
                  basket encoding, MXU pair-support counting (``XᵀX``),
                  itemset extension, rule-tensor emission, and the serve-time
                  gather → max-merge → top-k recommendation kernel.
- ``parallel/`` — device-mesh sharding of the mining compute: data-parallel
                  ``psum`` over the transaction axis, tensor-parallel sharding
                  of the item axis with all-gather and ring (``ppermute``)
                  pair-count variants riding ICI.
- ``mining/``   — the batch job (reference: machine-learning/main.py:421-484):
                  dataset rotation, vocab building, device mining, artifact
                  emission, run-history bookkeeping.
- ``serving/``  — the online API (reference: rest_api/app/main.py): identical
                  HTTP surface served from HBM-resident rule tensors with a
                  double-buffered hot swap driven by the same polling protocol.
- ``models/``   — the model abstraction: rule tensors + vocabulary + jitted
                  apply as one deployable object, in two families
                  (support-mode / confidence-mode semantics).
- ``io/``       — artifact + state files: the pickle wire format the reference
                  serves from, dataset registry, run history, invalidation
                  token (reference: machine-learning/main.py:315-411).
- ``data/``     — CSV ingestion and synthetic basket generation.
- ``utils/``    — env contract, dotenv, timestamps, logging.

Nothing here is a line translation of the reference: the FP-tree
(pointer-chasing, recursion — hostile to XLA) is replaced by an exact dense /
bit-packed formulation; see ``ops/support.py`` for the dominance argument that
makes pair counting sufficient for the reference's output semantics.
"""

__version__ = "0.1.0"
