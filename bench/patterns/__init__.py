"""Collective patterns of a job, one file per pattern, found by the
``pattern`` name of a traffic mix.  Each holds ``phases(n_ranks,
**pattern_args)``, which returns the job's phases as [(src ranks, dst
ranks, bytes)], and ``ALLTOALL``, whether Algorithm 1 treats the pattern
as an all-to-all."""
