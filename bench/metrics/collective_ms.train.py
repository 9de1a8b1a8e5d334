"""Device time per training step in collective operations, in ms, per
chip: all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute, whole or as asynchronous start and done
(``bench/collectives.py``), averaged over the chips. On a mesh these are
the weights' gathers, the tensor-parallel all-reduces, the constraint
stacks' reshards and the group step's psum. None where none ran."""

from bench import collectives


def read(run):
    return collectives.collective_ms(run)
