"""Any-to-any model converter CLI (reference: utils/ConvertModel.scala —
`--from bigdl|caffe|torch|tf --to ...`).

    python -m bigdl_tpu.interop.convert --input m.bigdl-tpu --output m.caffemodel
    python -m bigdl_tpu.interop.convert --input m.bigdl-tpu --output w.t7

Formats are inferred from extensions: .bigdl-tpu (full module+weights),
.caffemodel (weights; a .prototxt topology is written next to it on
export and used automatically on import when present), .t7 (weight
table — importing it back requires the module definition via --module,
like the reference requires the model code)."""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def _fmt(path: str, writable: bool = False) -> str:
    if os.path.isdir(path):
        if writable:
            raise ValueError(
                f"{path!r} is a directory — SavedModel is an INPUT "
                "format only (export via .pb / tf_saver instead)")
        # a TF2 SavedModel directory (saved_model.pb inside)
        return "saved_model"
    for ext, fmt in ((".bigdl-tpu", "bigdl"), (".caffemodel", "caffe"),
                     (".t7", "torch"), (".onnx", "onnx"), (".pb", "tf")):
        if path.endswith(ext):
            return fmt
    raise ValueError(f"cannot infer format of {path!r} "
                     f"(.bigdl-tpu | .caffemodel | .t7 | .onnx | .pb | "
                     f"SavedModel dir)")


def _params_to_table(params, prefix=""):
    out = {}
    for k, v in params.items():
        key = f"{prefix}{k}"
        if isinstance(v, dict):
            out.update(_params_to_table(v, key + "."))
        else:
            out[key] = np.asarray(v)
    return out


def _table_to_params(table, skeleton):
    """Overlay a flat weight table onto the module's param skeleton (keeps
    empty subtrees of parameterless layers intact)."""
    def copy(t):
        return {k: copy(v) for k, v in t.items()} if isinstance(t, dict) \
            else t
    root = copy(skeleton)
    for key, v in table.items():
        parts = key.split(".")
        d = root
        for p in parts[:-1]:
            d = d.setdefault(p, {})
        d[parts[-1]] = v
    return root


def convert(input_path: str, output_path: str, module_path: str = None,
            example_shape=None):
    from bigdl_tpu.utils.serializer import load_module, save_module
    src, dst = _fmt(input_path), _fmt(output_path, writable=True)

    if src == "bigdl":
        module, params, state = load_module(input_path)
    elif src == "onnx":
        from bigdl_tpu.interop.onnx import load_model as load_onnx
        module, params, state, _ = load_onnx(input_path)
    elif src == "tf":
        from bigdl_tpu.interop.tf_convert import load_model as load_tf
        module, params, state, _ = load_tf(input_path)
    elif src == "saved_model":
        from bigdl_tpu.interop.tf_saved_model import load_saved_model
        module, params, state, _ = load_saved_model(input_path)
    else:
        sibling_proto = input_path[:-len(".caffemodel")] + ".prototxt" \
            if src == "caffe" else None
        if not module_path and sibling_proto and os.path.exists(
                sibling_proto):
            # the pair our own caffe export writes: topology comes from
            # the prototxt, no module skeleton needed
            from bigdl_tpu.interop import caffe_proto
            net = caffe_proto.load(sibling_proto, input_path)
            module, params, state = net.module, net.params, net.state
        elif not module_path:
            raise ValueError(f"importing from {src} needs --module "
                             f"(a .bigdl-tpu file providing the topology)"
                             + (f" or a sibling {sibling_proto}"
                                if sibling_proto else ""))
        else:
            module, params, state = load_module(module_path)
            if src == "caffe":
                from bigdl_tpu.interop.caffe import load_caffe
                params = load_caffe(module, params, input_path)
            elif src == "torch":
                from bigdl_tpu.interop import torchfile
                params = _table_to_params(torchfile.load(input_path),
                                          params)

    if dst == "onnx":
        raise ValueError("onnx is an import-only format (like the "
                         "reference's onnx_loader)")
    if dst == "tf":
        from bigdl_tpu.interop.tf_saver import save_model as save_tf
        example = (np.zeros(tuple(example_shape), np.float32)
                   if example_shape else None)
        save_tf(output_path, module, params, state, example_input=example)
        print(f"converted {input_path} ({src}) -> {output_path} (tf)")
        return
    if dst == "bigdl":
        save_module(output_path, module, params, state)
    elif dst == "caffe":
        # full persist: prototxt topology next to the caffemodel
        # (reference: utils/caffe/CaffePersister.scala saveCaffe)
        from bigdl_tpu.interop.caffe_saver import save_caffe
        proto_path = output_path[:-len(".caffemodel")] + ".prototxt"
        example = (np.zeros(tuple(example_shape), np.float32)
                   if example_shape else None)
        save_caffe(proto_path, output_path, module, params, state,
                   example_input=example)
    elif dst == "torch":
        from bigdl_tpu.interop import torchfile
        torchfile.save(output_path, _params_to_table(params))
    print(f"converted {input_path} ({src}) -> {output_path} ({dst})")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="bigdl_tpu.interop.convert")
    ap.add_argument("--input", required=True)
    ap.add_argument("--output", required=True)
    ap.add_argument("--module", default=None,
                    help="topology .bigdl-tpu when importing caffe/t7")
    ap.add_argument("--example-shape", default=None,
                    help="comma-separated input shape (incl. batch) used "
                         "to resolve Flatten feature counts on tf/caffe "
                         "export, e.g. 1,28,28,1")
    args = ap.parse_args(argv)
    shape = ([int(d) for d in args.example_shape.split(",")]
             if args.example_shape else None)
    convert(args.input, args.output, args.module, example_shape=shape)


if __name__ == "__main__":
    sys.exit(main())
