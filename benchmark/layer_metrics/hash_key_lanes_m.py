"""Layer: fused runner. Millions of lanes ONE statement's device program
passes through key sorts under the HASHED key: the window's `rows` of
stage `fused.hash_key_lanes` over its events (one event a dispatch). A
join on one integer column sorts the key itself, in a u32 operand where
it fits; a join on two columns (Q9's partsupp join) or on a key that is no
integer sorts a 62-bit hash in a u64 operand and verifies each match by a
row-matrix gather of the build's key columns. The program reckons the
lanes from the traced shapes when it compiles: probe plus build capacity
of every join lowered with the hashed key. It is to that packing what
`sort_lanes_m` is to the sorts: a plan or packing change moves it before
it moves a millisecond; 0 when every join of the program keys on an
integer. A program without the stage has nothing to read here.
Source: program counter (the stage's rows and events)."""


def read(ctx):
    stage = ctx["window"]["stages"].get("fused.hash_key_lanes")
    if not stage or not stage.get("events"):
        return None
    return stage["rows"] / stage["events"] / 1e6
