"""monoidax — the paper's monoid principle as the aggregation layer of a
multi-pod JAX training/inference framework.

Subpackages:
  core        the Monoid abstraction, zoo, folds, MapReduce engine
  models      the 10-arch pure-JAX model substrate
  configs     assigned architectures x input-shape cells
  dist        logical-axis sharding rules
  optim       AdamW, schedules, EF gradient compression
  data        deterministic pipeline + sketch statistics
  checkpoint  atomic/async/mesh-agnostic checkpoints
  runtime     preemption / elastic re-mesh / stragglers
  kernels     Pallas TPU kernels (+ interpret-mode validation)
  launch      meshes, step builders, dry-run, roofline analyzer
  serving     continuous-batching serve engine (stable public facade)

The core fold API is re-exported here — ``repro.Monoid``,
``repro.execute_fold``, ``repro.plan_fold``, ``repro.MapReduceJob`` — so
applications don't import from ``repro.core.plan`` internals.
"""
from .core import (MapReduceJob, Monoid, execute_fold, monoids,
                   plan_fold)

__version__ = "0.1.0"

__all__ = ["MapReduceJob", "Monoid", "execute_fold", "monoids", "plan_fold",
           "__version__"]
