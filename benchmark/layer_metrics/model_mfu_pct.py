"""Model (`models/gpt.py`): forward + backward FLOPs a token from the
shape formula of the run's family (`benchmark/families/`; recomputation
not counted) x tokens a second over the chip's bf16 peak."""


def read(run):
    if run.get("kind") != "train":
        return None
    per_token = run["family"].train_flops_per_token(
        run["dims"], run["traffic"]["seq"])
    peak = run["device"]["peaks"]["bf16_flops_per_s"] * run["device"]["count"]
    return 100.0 * per_token * run["tokens_per_s"] / peak
