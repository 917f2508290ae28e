"""The readers of the programs' heaps (``benchmark/program_memory.py``)
over a hand-made compile ledger and over what the program's own ledger
writes."""

import copy
import json

import pytest

from benchmark import harness, program_memory

METRICS = ["grad_program_heap_gb", "infer_program_heap_gb",
           "program_heap_peak_gb", "remat_heap_misreckoned_pct"]
BYTES = ["argument_bytes", "output_bytes", "alias_bytes", "code_bytes"]


def exe(temp, **label):
    return {"label": label, "cache": "hit", "secs": 1.0, "temp_bytes": temp,
            "peak_bytes": None if temp is None else temp + 10,
            **dict.fromkeys(BYTES, None if temp is None else 1)}


def row(*executables):
    return {"n_trace": 1, "n_lower": 1, "n_compile": len(executables),
            "max_secs": 1.0, "executables": list(executables)}


@pytest.fixture
def records():
    return {"setup_split": {"compile_cache_after_warmup": {
        "hits": 7, "misses": 0, "compile_secs": 2.0, "busy_secs": 5.0,
        "programs": {
            "train_grad_sliced": row(
                # reckoned 10 % over (against the grid's larger program:
                # the one without a carry needs less), 25 % under,
                # exactly; one without a reckoning (remat off) and one the
                # ledger never found
                exe(500_000_000, grid="1x4096", remat="full", carry=False,
                    reckoned_heap_bytes=2_200_000_000),
                exe(2_000_000_000, grid="1x4096", remat="full", carry=True,
                    reckoned_heap_bytes=2_200_000_000),
                exe(4_000_000_000, grid="1x8192", remat="full",
                    reckoned_heap_bytes=3_000_000_000),
                exe(1_000_000_000, grid="1x2048", remat="matmuls",
                    reckoned_heap_bytes=1_000_000_000),
                exe(500_000_000, grid="1x1024", remat=False),
                exe(None, grid="1x512", remat="full",
                    reckoned_heap_bytes=9_000_000_000)),
            "infer_forward": row(exe(1_500_000_000, grid="1x4096"),
                                 exe(3_500_000_000, grid="1x8192")),
            "train_apply": row(exe(4_250_000_000)),
            "convert_element_type": row(exe(0), exe(0)),
        }}}}


def read_all(records):
    return {m: harness.metric_reader(m)(records) for m in METRICS}


def test_the_four_values_by_hand(records):
    assert read_all(records) == {
        "grad_program_heap_gb": 4.0,
        "infer_program_heap_gb": 3.5,
        "program_heap_peak_gb": 4.25,       # train_apply's
        "remat_heap_misreckoned_pct": 25.0,  # the 1x8192 grid's
    }


def without(records, what):
    """Records of a program that lacks part of what the readers read."""
    records = copy.deepcopy(records)
    split = records["setup_split"]
    led = split["compile_cache_after_warmup"]
    if what == "no records":
        return {}
    if what == "no set-up split":
        return {"setup_split": {}}
    if what == "no ledger":
        split["compile_cache_after_warmup"] = {"hits": 7, "misses": 0}
    elif what == "the parent's ledger":
        for r in led["programs"].values():
            del r["executables"]
    elif what == "no statistics":
        for r in led["programs"].values():
            r["executables"] = [exe(None, **e["label"])
                                for e in r["executables"]]
    return records


@pytest.mark.parametrize("what", ["no records", "no set-up split",
                                  "no ledger", "the parent's ledger",
                                  "no statistics"])
def test_a_program_without_the_fields_reads_none(records, what):
    assert set(read_all(without(records, what)).values()) == {None}


def test_remat_off_leaves_only_the_misreckoning_out(records):
    grad = records["setup_split"]["compile_cache_after_warmup"][
        "programs"]["train_grad_sliced"]
    grad["executables"] = [exe(500_000_000, grid="1x1024", remat=False)]
    got = read_all(records)
    assert got["remat_heap_misreckoned_pct"] is None
    assert got["grad_program_heap_gb"] == 0.5


def test_a_cell_without_a_program_leaves_its_metric_out(records):
    del records["setup_split"]["compile_cache_after_warmup"]["programs"][
        "infer_forward"]
    got = read_all(records)
    assert got["infer_program_heap_gb"] is None
    assert got["program_heap_peak_gb"] == 4.25


def test_the_programs_own_ledger_is_what_the_readers_read():
    """Through ``CacheStats`` itself: what it dumps, as JSON, reads back."""
    from areal_tpu.base import compile_watch as cw

    if not hasattr(cw, "MEMORY_FIELDS"):
        pytest.skip("this program's ledger keeps no executables")

    class Stats:
        argument_size_in_bytes = output_size_in_bytes = 8
        alias_size_in_bytes = generated_code_size_in_bytes = 0

        def __init__(self, temp):
            self.temp_size_in_bytes = temp
            self.peak_memory_in_bytes = temp + 16

    class Exe:
        def __init__(self, name, temp):
            self.name, self.temp, self.fingerprint = name, temp, str(temp)

        def hlo_modules(self):
            return [self]

        def get_compiled_memory_stats(self):
            return Stats(self.temp)

    live = []
    ledger = cw.CacheStats(lambda: list(live))
    comp = "/jax/core/compile/backend_compile_duration"
    for fn, temp, label in [
            ("train_grad_sliced", 3_000_000_000,
             dict(grid="2x512", remat="full",
                  reckoned_heap_bytes=4_500_000_000)),
            ("infer_forward", 1_000_000_000, dict(grid="2x512"))]:
        cw.label(fn, **label)
        ledger._on_enter(comp, 1.0, fun_name=f"jit({fn})")
        live.insert(0, Exe("jit_" + fn, temp))  # born inside the compile
        ledger._on_span(comp, 1.0, 2.0, fun_name=f"jit({fn})")
    led = json.loads(json.dumps(ledger.as_dict()))
    records = {"setup_split": {"compile_cache_after_warmup": led}}
    assert read_all(records) == {
        "grad_program_heap_gb": 3.0, "infer_program_heap_gb": 1.0,
        "program_heap_peak_gb": 3.0, "remat_heap_misreckoned_pct": 50.0}
    assert len(program_memory.executables(records)) == 2
