"""The sharing scheme a configuration file states, built by the program
and held to what the file says -- shared by the drivers."""

from __future__ import annotations


def packed_shamir(config: dict):
    """``PackedShamirSharing`` from the configuration's ``scheme`` block.
    The program derives threshold and prime from (k, n, bits); a
    configuration that states others is wrong, not overridden."""
    from sda_tpu.fields import numtheory
    from sda_tpu.protocol import PackedShamirSharing

    want = config["scheme"]
    if want["kind"] != "packed_shamir":
        raise ValueError(f"scheme kind {want['kind']!r} is not packed_shamir")
    k, n = want["secret_count"], want["share_count"]
    t, p, w2, w3 = numtheory.generate_packed_params(k, n, want["prime_bits"])
    if (t, p) != (want["privacy_threshold"], want["prime_modulus"]):
        raise ValueError(f"configuration states t={want['privacy_threshold']}, "
                         f"p={want['prime_modulus']}; the program derives {(t, p)}")
    return PackedShamirSharing(k, n, t, p, w2, w3)
