"""Checks that nothing quietly leaves the chip (from `chip_smoke.py`'s
preflight): on a TPU the "auto" datapaths must resolve to the Pallas
kernels, kernels must not run in interpret mode, and the programs the
window drives must carry compiled kernels (``tpu_custom_call``)."""

from __future__ import annotations


class LeftTheChip(RuntimeError):
    """A measured path would run off the chip's kernels."""


def check_backend(run, encoder: str) -> None:
    if not run.on_tpu:
        return
    from repro.core import registry
    from repro.kernels import ops
    from repro.serving.execution import resolve_impl

    got = registry.resolve_backend("auto", encoder=encoder)
    if got != "pallas":
        raise LeftTheChip(f"encoder {encoder!r}: auto backend is {got!r}, not pallas")
    if resolve_impl("auto") != "pallas":
        raise LeftTheChip(f"packed impl auto is {resolve_impl('auto')!r}, not pallas")
    if ops._interpret_default():
        raise LeftTheChip("Pallas kernels default to interpret mode")


def native(run, lowered, what: str) -> None:
    if run.on_tpu and "tpu_custom_call" not in lowered.as_text():
        raise LeftTheChip(f"{what}: no compiled Pallas kernel in the program")
