"""Layer: scan images + storage. Bytes moved to the device by the first
execution (scan.transfer, serving.image_build, resident.h2d), in MB.
The serving image build counts no bytes, so a batched cell has nothing to
read here."""


def read(ctx):
    moved = sum(f["prime_bytes"] for f in ctx["first"])
    return moved / 1e6 if moved else None
