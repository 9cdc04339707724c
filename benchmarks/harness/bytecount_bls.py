"""Bytes the certificate Miller program has to read, from shapes alone:
the same whatever engine or layout implements it."""

#: consumed slots of the Miller schedule over |x| (63 doublings, 5
#: additions), coefficients a line (slope, constant), Fp12 coefficients,
#: limbs an Fp element, bytes a limb
SLOTS, LINE_COEFFS, FP12, LIMBS, LIMB_BYTES = 68, 2, 12, 33, 4
#: a pair's point (x, y), its key index and its pad flag
POINT_BYTES = 2 * LIMBS * LIMB_BYTES + 4 + 1


def miller_bytes(rows: int) -> int:
    """One dispatch of ``rows`` pairs: each pair's gathered line rows
    (every slot's slope and constant) and its point."""
    line_rows = SLOTS * LINE_COEFFS * FP12 * LIMBS * LIMB_BYTES
    return rows * (line_rows + POINT_BYTES)
