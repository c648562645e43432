"""The census of ``PTPU_*`` environment switches (ISSUE 32).

Every switch doubles what a test matrix would have to cover, so the set is
written out here: a new switch arrives as a visible edit of this list, a
deleted one leaves it, and a name that is documented but read nowhere (or
read but named in no list) fails. ROADMAP.md's count is this list's length.
"""
import ast
import glob
import os
import re

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"PTPU_[A-Z0-9_]+")

SWITCHES = frozenset("""
PTPU_ADAM_FACTORED PTPU_AGENT_READY PTPU_CE_VCHUNK PTPU_COMM_BUCKET_MB
PTPU_COMM_SLAB PTPU_COMPOSED PTPU_FA_BLOCK PTPU_FA_BWD_BLOCK
PTPU_FA_BWD_KBLOCK PTPU_FA_FUSED_BWD PTPU_FLEET_HOSTS PTPU_FLEET_PROC
PTPU_FLIGHT_DIR PTPU_FUSED_FFN PTPU_HBM_BUDGET PTPU_INT8_HEAD
PTPU_INT8_HEAD_GATE_TOL PTPU_INT8_KV PTPU_INT8_KV_TOL PTPU_INT8_WEIGHTS
PTPU_LAYOUT_CACHE PTPU_LINK_GBPS PTPU_LOSS_HEAD PTPU_METRICS_HOST
PTPU_METRICS_PORT PTPU_OVERLOAD PTPU_PAGED_INT8_KERNEL PTPU_PALLAS_RMS
PTPU_PIPELINE_SCHEDULE PTPU_PLAN_CACHE PTPU_PUSH_STREAM
PTPU_QUANT_AMAX_HIST PTPU_QUANT_COLLECTIVES PTPU_QUANT_COMPUTE
PTPU_QUANT_DTYPE PTPU_QUANT_EXCLUDE PTPU_QUANT_GATE_TOL PTPU_QUANT_GRADS
PTPU_QUANT_MIN_NUMEL PTPU_QUANT_PARAM_GATHER PTPU_RECOMPILE_WARN
PTPU_RING_ATTN PTPU_RING_KERNEL PTPU_SCAN_LAYERS PTPU_SHARDED_HEAD
PTPU_TP_SEAM PTPU_TRACE PTPU_TRACE_BUFFER PTPU_WEIGHTS_HOME
PTPU_WORKER_READY PTPU_ZERO_JIT_GATHER PTPU_ZERO_MODE
""".split())
#: counted by the grep, but no environment variable: the first word of the
#: line a fleet worker / host agent prints when it is ready
HANDSHAKES = frozenset({"PTPU_AGENT_READY", "PTPU_WORKER_READY"})


def _sources():
    """The files ROADMAP.md's grep counts: the package, the root scripts
    that build or smoke the programs, and tools/."""
    files = [os.path.join(REPO, "bench.py"),
             os.path.join(REPO, "chip_smoke.py")]
    for top in ("paddle_tpu", "tools"):
        files += glob.glob(os.path.join(REPO, top, "**", "*.py"),
                           recursive=True)
    return files


def _text(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


def test_names_mentioned_in_the_code_are_the_census():
    named = set()
    for path in _sources():
        named.update(NAME.findall(_text(path)))
    assert named == SWITCHES, (sorted(named - SWITCHES),
                               sorted(SWITCHES - named))


def test_every_switch_of_the_census_is_read_by_code():
    """A name read by code is a string constant that IS the name (the
    argument of an ``os.environ`` call, an entry of a knob tuple); a name
    inside a docstring or a message is part of a longer string."""
    read = set()
    for path in _sources():
        for node in ast.walk(ast.parse(_text(path))):
            if (isinstance(node, ast.Constant) and isinstance(node.value, str)
                    and NAME.fullmatch(node.value)):
                read.add(node.value)
    env = SWITCHES - HANDSHAKES
    assert read == env, (sorted(read - env), sorted(env - read))


def test_documents_name_no_switch_outside_the_census():
    """README.md and docs/ (the round-by-round history apart) describe
    switches that exist."""
    docs = [os.path.join(REPO, "README.md")] + [
        p for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
        if not os.path.basename(p).startswith("ROUND")]
    stale = {}
    for path in docs:
        # ``PTPU_QUANT_*`` names a group: held to being some switch's prefix
        unknown = sorted(n for n in set(NAME.findall(_text(path))) - SWITCHES
                         if not (n.endswith("_")
                                 and any(s.startswith(n) for s in SWITCHES)))
        if unknown:
            stale[os.path.relpath(path, REPO)] = unknown
    assert not stale, stale
