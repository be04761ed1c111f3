"""SHA-256 written out plainly (FIPS 180-4), over any values with the
uint32 operators ``+ ^ & | >> <<``.

The benchmark's reference and its operation count both run on this one
function and on nothing of the program under test.  Values may be Python
ints (masked to 32 bits by the caller's type), numpy or jax uint32 arrays,
or the operation counter's abstract values.
"""

from __future__ import annotations

K = (
    0x428A2F98, 0x71374491, 0xB5C0FBCF, 0xE9B5DBA5, 0x3956C25B, 0x59F111F1,
    0x923F82A4, 0xAB1C5ED5, 0xD807AA98, 0x12835B01, 0x243185BE, 0x550C7DC3,
    0x72BE5D74, 0x80DEB1FE, 0x9BDC06A7, 0xC19BF174, 0xE49B69C1, 0xEFBE4786,
    0x0FC19DC6, 0x240CA1CC, 0x2DE92C6F, 0x4A7484AA, 0x5CB0A9DC, 0x76F988DA,
    0x983E5152, 0xA831C66D, 0xB00327C8, 0xBF597FC7, 0xC6E00BF3, 0xD5A79147,
    0x06CA6351, 0x14292967, 0x27B70A85, 0x2E1B2138, 0x4D2C6DFC, 0x53380D13,
    0x650A7354, 0x766A0ABB, 0x81C2C92E, 0x92722C85, 0xA2BFE8A1, 0xA81A664B,
    0xC24B8B70, 0xC76C51A3, 0xD192E819, 0xD6990624, 0xF40E3585, 0x106AA070,
    0x19A4C116, 0x1E376C08, 0x2748774C, 0x34B0BCB5, 0x391C0CB3, 0x4ED8AA4A,
    0x5B9CCA4F, 0x682E6FF3, 0x748F82EE, 0x78A5636F, 0x84C87814, 0x8CC70208,
    0x90BEFFFA, 0xA4506CEB, 0xBEF9A3F7, 0xC67178F2,
)
H0 = (
    0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
    0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19,
)


def rotr(x, n: int, u32):
    return (x >> u32(n)) | (x << u32(32 - n))


def compress(state, w, u32, outputs: int = 8):
    """One compression of the 16-word block ``w`` into ``state``.

    ``u32`` makes a 32-bit constant of the values' type.  ``outputs``
    returns only the first that many words of the new state, and skips the
    additions that feed none of them: the min-hash contract reads the first
    two (the digest's first 8 bytes).  ``ch`` and ``maj`` are written in
    their cheapest exact forms, so that the operation count is the least
    the hash needs."""
    w = list(w)
    for t in range(16, 64):
        s0 = rotr(w[t - 15], 7, u32) ^ rotr(w[t - 15], 18, u32) ^ (w[t - 15] >> u32(3))
        s1 = rotr(w[t - 2], 17, u32) ^ rotr(w[t - 2], 19, u32) ^ (w[t - 2] >> u32(10))
        w.append((w[t - 16] + s0) + (w[t - 7] + s1))
    a, b, c, d, e, f, g, h = state
    prev_ab = b ^ c
    for t in range(64):
        s1e = rotr(e, 6, u32) ^ rotr(e, 11, u32) ^ rotr(e, 25, u32)
        ch = g ^ (e & (f ^ g))
        t1 = h + s1e + ch + (u32(K[t]) + w[t])
        s0a = rotr(a, 2, u32) ^ rotr(a, 13, u32) ^ rotr(a, 22, u32)
        ab = a ^ b
        maj = b ^ (ab & prev_ab)
        prev_ab = ab
        t2 = s0a + maj
        if t == 63 and outputs <= 4:
            # The new e (d + t1) feeds only output words 4..7.
            new = (t1 + t2, a, b, c)
        else:
            new = (t1 + t2, a, b, c, d + t1, e, f, g)
        a, b, c, d = new[:4]
        if len(new) == 8:
            e, f, g, h = new[4:]
    out = (a, b, c, d, e, f, g, h)[:outputs]
    return tuple(s + x for s, x in zip(state[:outputs], out))
