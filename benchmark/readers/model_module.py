"""Reader kind ``model_module``: device time of a model's compiled programs
(by the XLA module names the declaration lists under ``modules``) or of one
of its kernels (by the names its custom call carries in the trace's ``XLA
Ops`` line, digits stripped as ``trace.op_kind`` does, under ``ops``) against
the work the model's family reckons for the slice, from the configuration's
numbers.

The declaration names the model ``group`` of the configuration.  The traffic
kind's ``slice_readings`` keeps, under that group's name in ``useful_tokens``,
one ``(prompt tokens, decode steps)`` for each request whose device work lies
inside the slice's clip.  Quantities:

- ``prefill_mfu_pct``     the family's ``prompt_flops`` of those prompts over
  the programs' device seconds x the chip's peak FLOP/s;
- ``decode_roofline_pct`` the least time a decode step could take, the
  family's ``decode_bytes`` at the steps' mean context over the chip's peak
  bytes/s (a step of one sequence is bound by the weights it reads), over the
  programs' mean execution time;
- ``kernel_roofline_pct`` the FLOPs the family's function named by ``work``
  gives for each prompt, over the chip's peak, over the seconds the ``ops``
  took (a kernel bound by the MXU: its bytes over peak bandwidth are a
  hundredth of that).  The reduction keeps the ten op kinds that took longest;
  a kernel that is not among them reads nothing.

Finds nothing (another program, another traffic kind), returns nothing.
"""

from __future__ import annotations

from benchmark import doors


def read(decl: dict, r: dict) -> float | None:
    if "ops" in decl:
        count, total_s = 1, sum(t for name, t in r["trace"]["ops"] if name in decl["ops"])
    else:
        found = [m for name, m in r["trace"]["modules"].items() if name in decl["modules"]]
        count, total_s = sum(m["count"] for m in found), sum(m["total_s"] for m in found)
    work = r["slice"]["useful_tokens"]
    work = work.get(decl["group"]) if isinstance(work, dict) else None
    group = r["config"].get(decl["group"])
    if not count or total_s <= 0 or not work or group is None:
        return None
    family = doors.family(group, f"the configuration's `{decl['group']}.family`")
    q = decl["quantity"]
    if q in ("prefill_mfu_pct", "kernel_roofline_pct"):
        per_prompt = family.prompt_flops if q == "prefill_mfu_pct" else getattr(family, decl["work"])
        flops = sum(per_prompt(group, prompt) for prompt, _steps in work)
        return 100.0 * flops / (total_s * r["peaks"]["bf16_flops_per_s"])
    if q == "decode_roofline_pct":
        steps = sum(s for _p, s in work)
        if not steps:
            return None
        context = sum(s * (p + (s + 1) / 2.0) for p, s in work) / steps
        least_s = family.decode_bytes(group, context) / r["peaks"]["hbm_bytes_per_s"]
        return 100.0 * least_s / (total_s / count)
    raise ValueError(f"model_module: unknown quantity {q!r}")
