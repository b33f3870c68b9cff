"""Helpers shared by the CPU check scripts (``tests/*_check.py``), which
find this module beside them.  It imports JAX only inside its functions,
so a script may import it before it sets ``XLA_FLAGS``."""

import dataclasses
import re


def small_model(name="bert-base-paper"):
    """``name`` from the registry at two layers of width 32 (an
    encoder-decoder with two encoder layers over 12 frames)."""
    from repro.configs import get_config
    from repro.models import build_model

    reg = get_config(name)
    small = dict(n_layers=2, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=128)
    if reg.enc_dec is not None:
        small["enc_dec"] = dataclasses.replace(reg.enc_dec, n_enc_layers=2, enc_seq=12)
    return build_model(dataclasses.replace(reg, **small))


def canonical_hlo(text):
    """Optimised HLO text without metadata, module name or instruction names."""
    text = re.sub(r",? metadata=\{[^}]*\}", "", text)
    # the stack-frame tables that metadata points into
    text = re.sub(r"^(FileNames|FunctionNames|FileLocations|StackFrames|\d+ .*)\n", "", text,
                  flags=re.M)
    text = re.sub(r"^HloModule \S+", "HloModule m", text, flags=re.M)
    names = {}
    for line_name in re.findall(r"%([\w.\-]+)", text):
        names.setdefault(line_name, f"v{len(names)}")
    return re.sub(r"%([\w.\-]+)", lambda m: "%" + names[m.group(1)], text)
