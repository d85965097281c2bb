"""Counter-based deterministic CRC outcomes + ARQ host reference.

Both engines draw every packet's per-attempt CRC outcome from the same
counter-based hash — no RNG state in the scan carry, no sequencing
between concurrent transmissions, and bitwise agreement between the
gather and scatter engines for free:

    fail(seed, pkt, attempt)  <=>  h16(seed, pkt, attempt) < perq[link]

where ``h16`` is the low 16 bits of a murmur3-finalizer mix over the
packet's unique id (``src_row * K + slot``) and the attempt counter, and
``perq`` is the link's packet error rate quantized onto ``[0, 2^16)``
(``phy.rates``).  Because the draw does not depend on the link, CRC
outcomes are *monotone in link quality*: lowering ``perq`` can only turn
failures into passes — which makes sweep comparisons across rate
policies well-behaved.

``crc_hash``/``crc_fail`` are dtype-generic (numpy arrays on the host,
traced ``jnp`` arrays inside the engines — uint32 wraparound arithmetic
in both).  ``reference_attempts`` is the host-side executable spec: the
exact attempt count and drop outcome per packet, which the property
tests compare against the engines' NACK/drop counters.
"""
from __future__ import annotations

import numpy as np


def _u32(x, like):
    """Constant ``x`` as a uint32 scalar of the operand's array library."""
    return like.dtype.type(x & 0xFFFFFFFF)


def _as_u32(x):
    """Cast host ints / numpy / traced arrays to uint32 uniformly."""
    if hasattr(x, "astype") and not isinstance(x, np.ndarray):
        return x.astype("uint32")               # jax traced array
    return np.asarray(x).astype(np.uint32)


def crc_hash(seed, uid, attempt):
    """Murmur3-finalizer mix of (seed, packet uid, attempt) -> uint32.

    Inputs may be numpy or jax arrays (any integer dtype); arithmetic is
    uint32 with wraparound, identical on host and device.
    """
    uid = _as_u32(uid)
    attempt = _as_u32(attempt)
    seed = _as_u32(seed)
    with np.errstate(over="ignore"):          # uint32 wraparound is the point
        x = uid * _u32(0x9E3779B9, uid) ^ seed \
            ^ (attempt * _u32(0x85EBCA6B, uid))
        x = x ^ (x >> _u32(16, x))
        x = x * _u32(0x85EBCA6B, x)
        x = x ^ (x >> _u32(13, x))
        x = x * _u32(0xC2B2AE35, x)
        x = x ^ (x >> _u32(16, x))
    return x


def crc_fail(seed, uid, attempt, perq):
    """Bool: does attempt ``attempt`` of packet ``uid`` fail CRC?

    ``perq`` is the link's quantized PER threshold (int, ``[0, 2^16)``);
    comparison happens in int32, matching the engines exactly.
    """
    h = crc_hash(seed, uid, attempt)
    h16 = (h & _u32(0xFFFF, h)).astype("int32")
    return h16 < perq


def reference_attempts(seed: int, uid, perq, max_retx: int):
    """Host reference: (attempts, delivered) per packet.

    Walks attempts ``0 .. max_retx - 1`` exactly as the engines do: the
    packet delivers on its first CRC pass; after ``max_retx`` failures it
    is dropped.  Returns the number of attempts actually transmitted and
    a delivered flag, both numpy arrays broadcast over ``uid``/``perq``.
    """
    uid = np.asarray(uid, np.int64)
    perq = np.asarray(perq, np.int64)
    uid, perq = np.broadcast_arrays(uid, perq)
    attempts = np.zeros(uid.shape, np.int64)
    delivered = np.zeros(uid.shape, bool)
    pending = np.ones(uid.shape, bool)
    for a in range(max_retx):
        fail = np.asarray(crc_fail(seed, uid, np.full(uid.shape, a),
                                   perq.astype(np.int32)))
        attempts[pending] += 1
        delivered |= pending & ~fail
        pending &= fail
    return attempts, delivered
