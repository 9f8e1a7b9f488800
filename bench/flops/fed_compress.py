"""Bytes the fed_compress kernel moves for one upload transform.

Each of the K grid steps reads one client's float32 error-feedback row of
P values, and writes P int8 codes and one 128-lane float32 row that holds
the scale.  The threshold search works on the row once it is in VMEM, so
HBM traffic is one read and one write of the row; its compare-and-count
passes run on the vector unit, not the matrix unit whose peak the table
holds, and are not counted: the roofline is the bandwidth bound."""


def cost(K: int, P: int):
    """(operations, bytes) of one call."""
    return 0, K * P * 4 + K * P + K * 128 * 4
