"""Distributed training runtime.

The subsystem that promotes the `parallel/` tree learners into the
first-class `engine.train` / CLI path: mesh construction and learner
selection (`runtime.py`), distributed bin-boundary finding mirroring the
reference's ``GlobalSyncUpByMin/Max`` + sample sync (`binning.py`), and
sharded-score checkpoint rescatter. The reference implements this plane
in `src/network/` (Allreduce/ReduceScatter/Allgather over MPI sockets);
here every collective is an XLA op inside one jitted SPMD program,
lowered to ICI all-reduces on real hardware.
"""
__all__ = ["runtime", "binning"]
