"""Bytes a kernel has to move, from shapes alone — the same whatever
engine or table layout implements it."""

#: 4-bit windows over a 256-bit scalar
COMB_WINDOWS = 64
#: extended coordinates of a table entry, limbs a coordinate, bytes a limb
COORDS, LIMBS, LIMB_BYTES = 4, 22, 4
#: a row's transfer: 131 B of packed nibbles and flags, 23 int32 of R
ROW_INPUT_BYTES = 131 + 23 * 4
ROW_MASK_BYTES = 1


def comb_walk_bytes(bucket: int) -> int:
    """The fixed-key comb walk of one dispatch of ``bucket`` rows: per
    row and window one key-table entry and one base-table entry, plus
    the row's inputs and its mask byte."""
    entry = COORDS * LIMBS * LIMB_BYTES
    per_row = COMB_WINDOWS * 2 * entry + ROW_INPUT_BYTES + ROW_MASK_BYTES
    return bucket * per_row
