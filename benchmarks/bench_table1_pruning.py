"""Table I reproduction: test error of LAKP- vs KP-pruned CapsNet at
matched survived-weight rates (synthetic digits/fashion stand-ins; the
claim STRUCTURE is relative — LAKP <= KP error, gap growing with sparsity).
"""

from __future__ import annotations

from benchmarks import common as bc
from repro.deploy import FastCapsPipeline


def run(quick: bool = True) -> dict:
    cfg = bc.bench_capsnet_cfg(quick)
    steps = 80 if quick else 300
    ft_steps = 40 if quick else 150
    sparsities = [0.5, 0.8, 0.95] if quick else [0.5, 0.8, 0.9, 0.95, 0.99]
    out = {}
    rows = []
    for variant in (["digits"] if quick else ["digits", "fashion"]):
        params, data = bc.train_capsnet(cfg, variant, steps)
        base_err = bc.test_error(params, cfg, data)
        for s in sparsities:
            errs = {}
            for method in ("kp", "lakp"):
                pipe = FastCapsPipeline(cfg, params=params)
                pipe.prune(s, s, method=method).finetune(
                    bc.finetune_fn_factory(cfg, data, ft_steps))
                # masked-dense (pre-compaction) params score the error
                errs[method] = bc.test_error(pipe.params, cfg, data)
            gain = (errs["kp"] - errs["lakp"]) / max(errs["kp"], 1e-9) * 100
            rows.append([variant, f"{base_err:.2f}",
                         f"{(1-s)*100:.1f}%", f"{errs['kp']:.2f}",
                         f"{errs['lakp']:.2f}", f"{gain:+.1f}%"])
            out[(variant, s)] = errs
    bc.print_table(
        "Table I: test error (%) — KP vs proposed LAKP",
        ["dataset", "dense err", "survived", "KP", "LAKP (ours)",
         "rel gain"], rows)
    return out


if __name__ == "__main__":
    bc.enable_compile_cache()
    run(quick=True)
