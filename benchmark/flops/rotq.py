"""Operations and bytes the rotq codec's rotations need, from their shapes.

A round rotates every client's row twice (into the rotated basis before
quantising, back after). A row is the model's parameters padded to a power
of two, ``h``; the rotation is a Hadamard transform of length ``h``:
``h log2(h)`` additions, and the row read once and written once in float32.
That is the least any form of it moves: the Kronecker-factored products of
``ops/pallas_kernels.py`` pass over the row once a factor (three for 2^20),
a one-pass kernel once.
"""


def padded_row(n_params):
    return 1 << (n_params - 1).bit_length()


ROTATIONS = 2  # a round: forward before quantising, inverse after


def rotations_per_round(clients, n_params):
    """``(operations, bytes)`` of a round's rotations."""
    h = padded_row(n_params)
    flops = ROTATIONS * clients * h * (h.bit_length() - 1)
    nbytes = ROTATIONS * clients * h * 4 * 2  # read + write
    return flops, nbytes
