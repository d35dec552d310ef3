"""``scripts/same_program.py``: how a PR shows that a cell's program did not
move. Nothing is lowered here (a lowering takes 10 s to two minutes a cell):
the renumbering of private functions' suffixes and ``--compare``'s outcomes,
on hand-made lines."""

import importlib.util
import json
import os

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tool():
    spec = importlib.util.spec_from_file_location(
        "same_program", os.path.join(REPO, "scripts", "same_program.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_private_suffixes_are_renumbered_and_compare_tells_equal_from_moved(tmp_path, capsys):
    tool = _tool()
    # jax numbers private functions by a process-wide counter: the same program after an
    # unrelated earlier trace has other suffixes, in the same order of appearance
    one = "func.func private @_where_17(%a) { call @_where_17 call @clip_3 }\ncall @_where_18 @main"
    other = "func.func private @_where_41(%a) { call @_where_41 call @clip_9 }\ncall @_where_44 @main"
    assert one != other and tool.renumbered(one) == tool.renumbered(other)
    assert tool.renumbered(one) == (
        "func.func private @_where_n0(%a) { call @_where_n0 call @clip_n1 }\ncall @_where_n2 @main")
    swapped = other.replace("@_where_44", "@_where_41")  # another call graph: another text
    assert tool.renumbered(swapped) != tool.renumbered(one)
    assert tool.renumbered("tpu_custom_call backend_config = 12_34") == (
        "tpu_custom_call backend_config = 12_34")  # only names after an @

    def lines(path, **texts):
        path.write_text("".join(json.dumps(tool.hash_line(cell, text)) + "\n"
                                for cell, text in texts.items()))
        return str(path)

    parent = lines(tmp_path / "parent.jsonl", a=one, b="@main tpu_custom_call", c="x")
    same = lines(tmp_path / "same.jsonl", a=other, b="@main tpu_custom_call", c="x")
    moved = lines(tmp_path / "moved.jsonl", a=swapped, b="@main tpu_custom_call")
    assert tool.hash_line("b", "@main tpu_custom_call")["mosaic_calls"] == 1
    assert tool.main(["--compare", parent, same]) == 0
    assert "3 of 3 cells equal" in capsys.readouterr().out
    assert tool.main(["--compare", parent, moved]) == 1
    out = capsys.readouterr().out
    assert "a: " in out and "c: only in " in out and "\nb:" not in out and "1 of 3 cells equal" in out
