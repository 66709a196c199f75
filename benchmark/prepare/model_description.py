"""Set-up step `model_description`: write the model DESCRIPTION file
the completion daemon's --model option reads, from the configuration's
own keys — the architecture at its PUBLISHED values (the file's
`model_keys`, with `published` over the reduced ones), the `share` this
chip holds and the run's seed for the weights.  Returns {"model_file":
<path>} for the daemon's argv and "weights_seed" for the reference."""
import json
import os


def prepare(st, cfg: dict, step: dict, seed: int, work_dir: str) -> dict:
    arch = {k: cfg[k] for k in cfg["model_keys"]}
    arch.update(cfg.get("published", {}))
    desc = {"architecture": arch, "share": cfg["share"], "seed": int(seed)}
    path = os.path.join(work_dir, "model.json")
    with open(path, "w") as f:
        json.dump(desc, f, indent=1)
    return {"model_file": path, "weights_seed": int(seed)}
