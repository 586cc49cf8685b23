"""scripts/hash_programs.py: the tool a ``perf_opt`` PR uses to show which
serving programs it touched (optimized HLO less what an edit moves without
changing the program). Here: what it strips, and one case end to end with
this checkout on both sides."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "scripts"))

import hash_programs  # noqa: E402

MODULE = """HloModule jit__decode_impl, entry_computation_layout={(f32[2]{0})->f32[2]{0}}

FileNames
1 "/root/repo/datatunerx_tpu/models/hybrid.py"

FunctionNames
1 "forward"

ENTRY %main.3 (p: f32[2]) -> f32[2] {
  %p = f32[2]{0} parameter(0), metadata={op_name="x" source_file="a.py" source_line=LINE}
  ROOT %add.1 = f32[2]{0} add(%p, %p), metadata={op_name="jit(f)/dtx.attn/add" source_line=7}
}
"""


@pytest.mark.parametrize("edit,same", ids=["frame_table", "scope", "module_name", "an_op"], argvalues=[
    (lambda t: t.replace("hybrid.py", "llama.py"), True),     # a frame table
    (lambda t: t.replace("dtx.attn", "dtx.kv_write"), True),  # a scope
    (lambda t: t.replace("jit__decode_impl,", "jit__decode_impl.7,"), True),
    (lambda t: t.replace("add(%p, %p)", "multiply(%p, %p)"), False),
])
def test_a_hash_sees_the_program_and_not_where_it_was_written(edit, same):
    a = hash_programs._strip(MODULE.replace("LINE", "12"))
    b = hash_programs._strip(edit(MODULE.replace("LINE", "40")))
    assert "metadata" not in a and "FileNames" not in a and "ENTRY" in a
    assert (hash_programs._digest(a) == hash_programs._digest(b)) == same


def test_a_checkout_hashes_as_itself():
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "hash_programs.py"),
         "--parent", ROOT, "--only", "kernel/"],
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln.split() for ln in out.stdout.splitlines() if ln.startswith("   ")]
    assert len(lines) == len(hash_programs.KERNELS) and all(ln[1] == "SAME" for ln in lines), out.stdout


def test_a_session_hashes_as_itself_on_the_other_modules_line():
    """Beside the four named programs, every other module a session compiled
    (the scheduler's eager scatters and slices among them) is one line of
    count and digest: the same tree reads SAME there too."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "hash_programs.py"),
         "--parent", ROOT, "--only", "debug.gather"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("   ")]
    assert len(lines) >= 4, out.stdout
    assert all(" SAME " in ln for ln in lines), out.stdout
    other = [ln for ln in lines if hash_programs.OTHER in ln]
    assert len(other) == 1, out.stdout
    count = int(other[0].split("[")[1].split(",")[0])
    assert count > len(lines), other[0]  # dozens of eager modules, not four
