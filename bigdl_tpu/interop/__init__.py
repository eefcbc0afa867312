"""bigdl_tpu.interop — model format importers/exporters
(reference: utils/caffe/, utils/tf/, utils/TorchFile.scala,
utils/ConvertModel.scala, pyspark/bigdl/contrib/onnx/; SURVEY.md §2.8)."""

from bigdl_tpu.interop import (caffe, caffe_saver, glm_moe_dsa, huggingface,
                               keras_loader, olmo_hybrid, onnx, protowire,
                               tensorflow, tf_example, torchfile)
