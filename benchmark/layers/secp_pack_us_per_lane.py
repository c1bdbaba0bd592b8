"""Host seconds the stream spent packing secp256k1 launches (the scalar
products, SHA-256, checks and word layout of secp256k1_batch
.prepare_batch, and the pad), per secp256k1 lane that reached the
device: the program's book by curve over the window, carried by the
``mixed_commit`` generator."""

from benchmark.lib import curve_books

NAME = "secp_pack_us_per_lane"
UNIT = "us/lane"
BETTER = "lower"
SOURCE = "program_counter"
LAYER = "crypto.tpu.secp256k1_batch"
MOVES = "verdict_p50_ms"


def read(before: dict, after: dict, trace):
    book = curve_books.book(after, "secp256k1")
    lanes = float(book.get("device_lanes", 0))
    if lanes <= 0 or "pack_s" not in book:
        return None
    return float(book["pack_s"]) / lanes * 1e6
