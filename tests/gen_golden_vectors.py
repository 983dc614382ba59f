"""Generate golden transcript vectors for regression locking.

Run ``python tests/gen_golden_vectors.py`` to (re)write
``tests/data/golden-vdaf-vectors.json``.  The vectors pin every wire
artifact of deterministic transcripts (fixed nonces/rand/verify key) for
each VDAF family, so any unintended change to encodings, XOF derivations, or
field arithmetic fails tests/test_golden_vectors.py loudly.

These are SELF-GENERATED vectors: they lock the implementation against
drift, and the loader doubles as the harness for official
draft-irtf-cfrg-vdaf test vectors once those JSON files can be vendored
(no network access in this environment).
"""

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from janus_tpu.vdaf import pingpong as pp  # noqa: E402
from janus_tpu.vdaf.instances import vdaf_from_instance  # noqa: E402

OUT = os.path.join(os.path.dirname(__file__), "data", "golden-vdaf-vectors.json")

CASES = [
    ({"type": "Prio3Count"}, [0, 1, 1]),
    ({"type": "Prio3Sum", "bits": 8}, [3, 250]),
    ({"type": "Prio3Histogram", "length": 4, "chunk_length": 2}, [0, 3]),
    ({"type": "Prio3SumVec", "length": 3, "bits": 2, "chunk_length": 2}, [[1, 2, 3]]),
    (
        {
            "type": "Prio3SumVecField64MultiproofHmacSha256Aes128",
            "proofs": 2,
            "length": 3,
            "bits": 2,
            "chunk_length": 2,
        },
        [[0, 1, 2]],
    ),
]


def det_bytes(tag: str, n: int) -> bytes:
    """Deterministic pseudo-random bytes (NOT from the implementation under
    test: plain SHA-256 counter mode)."""
    import hashlib

    out = b""
    i = 0
    while len(out) < n:
        out += hashlib.sha256(f"{tag}/{i}".encode()).digest()
        i += 1
    return out[:n]


def transcript(desc, measurements):
    vdaf = vdaf_from_instance(desc)
    vk = det_bytes("verify_key", vdaf.VERIFY_KEY_SIZE)
    rows = []
    for i, m in enumerate(measurements):
        nonce = det_bytes(f"nonce/{i}", vdaf.NONCE_SIZE)
        rand = det_bytes(f"rand/{i}", vdaf.RAND_SIZE)
        public_share, input_shares = vdaf.shard(m, nonce, rand)
        l_state, l_msg = pp.leader_initialized(
            vdaf, vk, None, nonce, public_share, input_shares[0]
        )
        trans = pp.helper_initialized(
            vdaf, vk, None, nonce, public_share, input_shares[1], l_msg
        )
        h_state, h_msg = trans.evaluate(vdaf)
        finished = pp.leader_continued(vdaf, l_state, h_msg)
        rows.append(
            {
                "measurement": m,
                "nonce": nonce.hex(),
                "rand": rand.hex(),
                "public_share": vdaf.encode_public_share(public_share).hex(),
                "input_share_0": input_shares[0].encode(vdaf).hex(),
                "input_share_1": input_shares[1].encode(vdaf).hex(),
                "leader_init_message": l_msg.encode().hex(),
                "helper_transition": trans.encode(vdaf).hex(),
                "helper_finish_message": h_msg.encode().hex(),
                "out_share_0": vdaf.field.encode_vec(finished.out_share).hex(),
                "out_share_1": vdaf.field.encode_vec(h_state.out_share).hex(),
            }
        )
    return {"vdaf": desc, "verify_key": vk.hex(), "reports": rows}


def main():
    vectors = [transcript(desc, ms) for desc, ms in CASES]
    with open(OUT, "w") as f:
        json.dump(vectors, f, indent=1, sort_keys=True)
    print(f"wrote {OUT} ({len(vectors)} transcripts)")


if __name__ == "__main__":
    main()
