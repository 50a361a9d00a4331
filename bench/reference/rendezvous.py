"""Plain reference for the fleet's owner host of a tenant.

The configuration's router is rendezvous (highest-random-weight) hashing:
every host h of the n gets the score

    mix64((crc32(str(tenant)) * 0x100000001B3) ^ ((h + 1) * 0x9E3779B97F4A7C15))

(mod 2^64; mix64 the splitmix64 finaliser), and the tenant's owner is the
host of the highest score, the higher host id on a tie.  With no host
cordoned every host is live, so this is the owner of every request.
Nothing is imported from the program under test.
"""
from __future__ import annotations

import zlib

MASK = (1 << 64) - 1


def mix64(x: int) -> int:
    x &= MASK
    x ^= x >> 33
    x = (x * 0xFF51AFD7ED558CCD) & MASK
    x ^= x >> 33
    x = (x * 0xC4CEB9FE1A85EC53) & MASK
    return x ^ (x >> 33)


def owner(tenant, n_hosts: int) -> int:
    key = (zlib.crc32(str(tenant).encode("utf-8")) * 0x100000001B3) & MASK
    return max(range(n_hosts),
               key=lambda h: (mix64(key ^ ((h + 1) * 0x9E3779B97F4A7C15)), h))
