"""The timed path broken underneath a run (`run.py --fault <name>`): each
has to make `correct` come out false. They patch the one function of a
traffic generator through which every operation of the window goes.

  state_unchanged   the encode call returns without doing anything (the
                    warm-up pass's files stay, and they are right)
  answer_altered    one byte of one shard file flipped after each operation
  half_batch        the batch call leaves out half of its volumes
  operand_altered   one byte of a needle in the sealed `.dat` flipped before
                    the warm-up pass: program and reference then encode the
                    same wrong bytes, and only the walk of the `.dat`
                    against the seed can tell
"""

from __future__ import annotations

import os


def _flip(path: str, offset: int) -> None:
    with open(path, "r+b") as f:
        f.seek(offset)
        byte = f.read(1)
        f.seek(offset)
        f.write(bytes([byte[0] ^ 0x5A]))


def plant(name: str, generator) -> None:
    kind = generator.__name__.rsplit(".", 1)[-1]
    if kind == "grpc_loop":
        real = generator.call_rpc
        if name == "state_unchanged":
            generator.call_rpc = lambda ctx, vids: None if hasattr(ctx, "op_log") else real(ctx, vids)
        elif name == "answer_altered":
            def altered(ctx, vids):
                real(ctx, vids)
                if hasattr(ctx, "op_log"):  # the window's calls, not the warm-up's
                    _flip(ctx.base(vids[-1]) + ".ec11", 4099)
            generator.call_rpc = altered
        elif name == "half_batch":
            generator.call_rpc = lambda ctx, vids: real(
                ctx, vids[: max(1, len(vids) // 2)] if hasattr(ctx, "op_log") else vids)
        elif name == "operand_altered":
            def altered_first(ctx, vids):
                if not hasattr(ctx, "operand_altered"):
                    ctx.operand_altered = True
                    # inside the data of the first needle (the smallest has 1 KiB)
                    _flip(ctx.ref_dat(vids[0]), 8 + 16 + 4 + 100)
                real(ctx, vids)
            generator.call_rpc = altered_first
        else:
            raise ValueError(f"grpc_loop has no fault {name!r}")
    else:
        raise ValueError(f"no faults for generator {kind!r}")
