// GENERATED by cpp-package/OpWrapperGenerator.py — DO NOT EDIT.
// Fluent wrappers for every registered operator (parity: reference
// cpp-package/include/mxnet-cpp/op.h, generated the same way).
#ifndef MXNET_TPU_CPP_OP_HPP_
#define MXNET_TPU_CPP_OP_HPP_

#include <map>
#include <string>
#include <vector>

#include "operator.hpp"

namespace mxnet_tpu {
namespace cpp {

using OpAttrs = std::map<std::string, std::string>;

namespace detail {
inline Operator Build(const char* name, const std::vector<NDArray>& inputs,
                      const OpAttrs& attrs) {
  Operator op(name);
  for (const auto& kv : attrs) op.SetParam(kv.first, kv.second);
  for (const auto& a : inputs) op.SetInput(a);
  return op;
}
}  // namespace detail

// operator Activation
inline NDArray Activation(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Activation", inputs, attrs).Invoke();
}
// operator BatchNorm — inputs: data, gamma, beta, moving_mean, moving_var
inline std::vector<NDArray> BatchNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("BatchNorm", inputs, attrs).InvokeMulti();
}
// operator BilinearSampler
inline NDArray BilinearSampler(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("BilinearSampler", inputs, attrs).Invoke();
}
// operator BlockGrad
inline NDArray BlockGrad(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("BlockGrad", inputs, attrs).Invoke();
}
// operator CTCLoss
inline NDArray CTCLoss(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("CTCLoss", inputs, attrs).Invoke();
}
// operator Cast
inline NDArray Cast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Cast", inputs, attrs).Invoke();
}
// operator Concat
inline NDArray Concat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Concat", inputs, attrs).Invoke();
}
// operator Convolution — inputs: data, weight, bias
inline NDArray Convolution(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Convolution", inputs, attrs).Invoke();
}
// operator Correlation
inline std::vector<NDArray> Correlation(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Correlation", inputs, attrs).InvokeMulti();
}
// operator Crop
inline NDArray Crop(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Crop", inputs, attrs).Invoke();
}
// operator Deconvolution — inputs: data, weight, bias
inline NDArray Deconvolution(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Deconvolution", inputs, attrs).Invoke();
}
// operator DeformableConvolution
inline NDArray DeformableConvolution(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("DeformableConvolution", inputs, attrs).Invoke();
}
// operator Dropout
inline NDArray Dropout(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Dropout", inputs, attrs).Invoke();
}
// operator ElementWiseSum
inline NDArray ElementWiseSum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ElementWiseSum", inputs, attrs).Invoke();
}
// operator Embedding — inputs: data, weight
inline NDArray Embedding(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Embedding", inputs, attrs).Invoke();
}
// operator Flatten
inline NDArray Flatten(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Flatten", inputs, attrs).Invoke();
}
// operator FullyConnected — inputs: data, weight, bias
inline NDArray FullyConnected(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("FullyConnected", inputs, attrs).Invoke();
}
// operator GridGenerator
inline NDArray GridGenerator(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("GridGenerator", inputs, attrs).Invoke();
}
// operator GroupNorm — inputs: data, gamma, beta
inline NDArray GroupNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("GroupNorm", inputs, attrs).Invoke();
}
// operator IdentityAttachKLSparseReg
inline std::vector<NDArray> IdentityAttachKLSparseReg(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("IdentityAttachKLSparseReg", inputs, attrs).InvokeMulti();
}
// operator InstanceNorm — inputs: data, gamma, beta
inline NDArray InstanceNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("InstanceNorm", inputs, attrs).Invoke();
}
// operator L2Normalization
inline NDArray L2Normalization(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("L2Normalization", inputs, attrs).Invoke();
}
// operator LRN
inline NDArray LRN(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("LRN", inputs, attrs).Invoke();
}
// operator LayerNorm — inputs: data, gamma, beta
inline NDArray LayerNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("LayerNorm", inputs, attrs).Invoke();
}
// operator LeakyReLU
inline NDArray LeakyReLU(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("LeakyReLU", inputs, attrs).Invoke();
}
// operator LinearRegressionOutput — inputs: data, label
inline NDArray LinearRegressionOutput(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("LinearRegressionOutput", inputs, attrs).Invoke();
}
// operator LogisticRegressionOutput — inputs: data, label
inline NDArray LogisticRegressionOutput(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("LogisticRegressionOutput", inputs, attrs).Invoke();
}
// operator MAERegressionOutput — inputs: data, label
inline NDArray MAERegressionOutput(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MAERegressionOutput", inputs, attrs).Invoke();
}
// operator MakeLoss
inline NDArray MakeLoss(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MakeLoss", inputs, attrs).Invoke();
}
// operator MultiBoxDetection
inline NDArray MultiBoxDetection(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MultiBoxDetection", inputs, attrs).Invoke();
}
// operator MultiBoxPrior
inline NDArray MultiBoxPrior(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MultiBoxPrior", inputs, attrs).Invoke();
}
// operator MultiBoxTarget
inline std::vector<NDArray> MultiBoxTarget(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MultiBoxTarget", inputs, attrs).InvokeMulti();
}
// operator MultiProposal
inline std::vector<NDArray> MultiProposal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("MultiProposal", inputs, attrs).InvokeMulti();
}
// operator PSROIPooling
inline NDArray PSROIPooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("PSROIPooling", inputs, attrs).Invoke();
}
// operator Pad
inline NDArray Pad(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Pad", inputs, attrs).Invoke();
}
// operator Pooling
inline NDArray Pooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Pooling", inputs, attrs).Invoke();
}
// operator Proposal
inline std::vector<NDArray> Proposal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Proposal", inputs, attrs).InvokeMulti();
}
// operator RMSNorm — inputs: data, gamma
inline NDArray RMSNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("RMSNorm", inputs, attrs).Invoke();
}
// operator RNN
inline std::vector<NDArray> RNN(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("RNN", inputs, attrs).InvokeMulti();
}
// operator ROIAlign
inline NDArray ROIAlign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ROIAlign", inputs, attrs).Invoke();
}
// operator ROIPooling
inline NDArray ROIPooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ROIPooling", inputs, attrs).Invoke();
}
// operator Reshape
inline NDArray Reshape(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Reshape", inputs, attrs).Invoke();
}
// operator SVMOutput
inline NDArray SVMOutput(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SVMOutput", inputs, attrs).Invoke();
}
// operator SequenceLast
inline NDArray SequenceLast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SequenceLast", inputs, attrs).Invoke();
}
// operator SequenceMask
inline NDArray SequenceMask(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SequenceMask", inputs, attrs).Invoke();
}
// operator SequenceReverse
inline NDArray SequenceReverse(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SequenceReverse", inputs, attrs).Invoke();
}
// operator SliceChannel
inline std::vector<NDArray> SliceChannel(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SliceChannel", inputs, attrs).InvokeMulti();
}
// operator Softmax — inputs: data, label
inline NDArray Softmax(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("Softmax", inputs, attrs).Invoke();
}
// operator SoftmaxActivation
inline NDArray SoftmaxActivation(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SoftmaxActivation", inputs, attrs).Invoke();
}
// operator SoftmaxOutput — inputs: data, label
inline NDArray SoftmaxOutput(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SoftmaxOutput", inputs, attrs).Invoke();
}
// operator SpatialTransformer
inline NDArray SpatialTransformer(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SpatialTransformer", inputs, attrs).Invoke();
}
// operator SwapAxis
inline NDArray SwapAxis(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SwapAxis", inputs, attrs).Invoke();
}
// operator SyncBatchNorm
inline std::vector<NDArray> SyncBatchNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("SyncBatchNorm", inputs, attrs).InvokeMulti();
}
// operator UpSampling
inline NDArray UpSampling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("UpSampling", inputs, attrs).Invoke();
}
// operator _arange
inline NDArray _arange(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_arange", inputs, attrs).Invoke();
}
// operator _cond
inline std::vector<NDArray> _cond(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_cond", inputs, attrs).InvokeMulti();
}
// operator _contrib_AdaptiveAvgPooling2D
inline NDArray _contrib_AdaptiveAvgPooling2D(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_AdaptiveAvgPooling2D", inputs, attrs).Invoke();
}
// operator _contrib_BilinearResize2D
inline NDArray _contrib_BilinearResize2D(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_BilinearResize2D", inputs, attrs).Invoke();
}
// operator _contrib_DeformableConvolution
inline NDArray _contrib_DeformableConvolution(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_DeformableConvolution", inputs, attrs).Invoke();
}
// operator _contrib_MoEFFN
inline std::vector<NDArray> _contrib_MoEFFN(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_MoEFFN", inputs, attrs).InvokeMulti();
}
// operator _contrib_MultiBoxDetection
inline NDArray _contrib_MultiBoxDetection(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_MultiBoxDetection", inputs, attrs).Invoke();
}
// operator _contrib_MultiBoxPrior
inline NDArray _contrib_MultiBoxPrior(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_MultiBoxPrior", inputs, attrs).Invoke();
}
// operator _contrib_MultiBoxTarget
inline std::vector<NDArray> _contrib_MultiBoxTarget(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_MultiBoxTarget", inputs, attrs).InvokeMulti();
}
// operator _contrib_MultiProposal
inline std::vector<NDArray> _contrib_MultiProposal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_MultiProposal", inputs, attrs).InvokeMulti();
}
// operator _contrib_PSROIPooling
inline NDArray _contrib_PSROIPooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_PSROIPooling", inputs, attrs).Invoke();
}
// operator _contrib_Proposal
inline std::vector<NDArray> _contrib_Proposal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_Proposal", inputs, attrs).InvokeMulti();
}
// operator _contrib_ROIAlign
inline NDArray _contrib_ROIAlign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_ROIAlign", inputs, attrs).Invoke();
}
// operator _contrib_SparseEmbedding — inputs: data, weight
inline NDArray _contrib_SparseEmbedding(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_SparseEmbedding", inputs, attrs).Invoke();
}
// operator _contrib_SyncBatchNorm
inline std::vector<NDArray> _contrib_SyncBatchNorm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_SyncBatchNorm", inputs, attrs).InvokeMulti();
}
// operator _contrib_arange_like
inline NDArray _contrib_arange_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_arange_like", inputs, attrs).Invoke();
}
// operator _contrib_bipartite_matching
inline std::vector<NDArray> _contrib_bipartite_matching(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_bipartite_matching", inputs, attrs).InvokeMulti();
}
// operator _contrib_boolean_mask
inline NDArray _contrib_boolean_mask(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_boolean_mask", inputs, attrs).Invoke();
}
// operator _contrib_box_iou
inline NDArray _contrib_box_iou(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_box_iou", inputs, attrs).Invoke();
}
// operator _contrib_box_nms
inline std::vector<NDArray> _contrib_box_nms(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_box_nms", inputs, attrs).InvokeMulti();
}
// operator _contrib_causal_conv1d — inputs: data, weight, bias
inline NDArray _contrib_causal_conv1d(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_causal_conv1d", inputs, attrs).Invoke();
}
// operator _contrib_count_sketch
inline NDArray _contrib_count_sketch(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_count_sketch", inputs, attrs).Invoke();
}
// operator _contrib_dequantize
inline NDArray _contrib_dequantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_dequantize", inputs, attrs).Invoke();
}
// operator _contrib_div_sqrt_dim
inline NDArray _contrib_div_sqrt_dim(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_div_sqrt_dim", inputs, attrs).Invoke();
}
// operator _contrib_edge_id
inline NDArray _contrib_edge_id(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_edge_id", inputs, attrs).Invoke();
}
// operator _contrib_fft
inline NDArray _contrib_fft(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_fft", inputs, attrs).Invoke();
}
// operator _contrib_flash_attention
inline NDArray _contrib_flash_attention(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_flash_attention", inputs, attrs).Invoke();
}
// operator _contrib_getnnz
inline NDArray _contrib_getnnz(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_getnnz", inputs, attrs).Invoke();
}
// operator _contrib_gradientmultiplier
inline NDArray _contrib_gradientmultiplier(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_gradientmultiplier", inputs, attrs).Invoke();
}
// operator _contrib_group_adagrad_update
inline std::vector<NDArray> _contrib_group_adagrad_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_group_adagrad_update", inputs, attrs).InvokeMulti();
}
// operator _contrib_hawkesll
inline std::vector<NDArray> _contrib_hawkesll(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_hawkesll", inputs, attrs).InvokeMulti();
}
// operator _contrib_ifft
inline NDArray _contrib_ifft(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_ifft", inputs, attrs).Invoke();
}
// operator _contrib_index_array
inline NDArray _contrib_index_array(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_index_array", inputs, attrs).Invoke();
}
// operator _contrib_index_copy
inline NDArray _contrib_index_copy(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_index_copy", inputs, attrs).Invoke();
}
// operator _contrib_kda_scan — inputs: q, k, v, g, beta
inline NDArray _contrib_kda_scan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_kda_scan", inputs, attrs).Invoke();
}
// operator _contrib_moe_ffn
inline std::vector<NDArray> _contrib_moe_ffn(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_moe_ffn", inputs, attrs).InvokeMulti();
}
// operator _contrib_quadratic
inline NDArray _contrib_quadratic(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quadratic", inputs, attrs).Invoke();
}
// operator _contrib_quantize
inline std::vector<NDArray> _contrib_quantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantize", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantize_v2
inline std::vector<NDArray> _contrib_quantize_v2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantize_v2", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_act
inline std::vector<NDArray> _contrib_quantized_act(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_act", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_concat
inline std::vector<NDArray> _contrib_quantized_concat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_concat", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_conv
inline std::vector<NDArray> _contrib_quantized_conv(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_conv", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_elemwise_add
inline std::vector<NDArray> _contrib_quantized_elemwise_add(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_elemwise_add", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_flatten
inline std::vector<NDArray> _contrib_quantized_flatten(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_flatten", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_fully_connected
inline std::vector<NDArray> _contrib_quantized_fully_connected(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_fully_connected", inputs, attrs).InvokeMulti();
}
// operator _contrib_quantized_pooling
inline std::vector<NDArray> _contrib_quantized_pooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_quantized_pooling", inputs, attrs).InvokeMulti();
}
// operator _contrib_requantize
inline std::vector<NDArray> _contrib_requantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_requantize", inputs, attrs).InvokeMulti();
}
// operator _contrib_rms_norm — inputs: data, gamma
inline NDArray _contrib_rms_norm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_rms_norm", inputs, attrs).Invoke();
}
// operator _contrib_rotary_embedding — inputs: data, positions
inline NDArray _contrib_rotary_embedding(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_rotary_embedding", inputs, attrs).Invoke();
}
// operator _contrib_routed_experts — inputs: data, router_weight, w1, w3, w2
inline std::vector<NDArray> _contrib_routed_experts(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_routed_experts", inputs, attrs).InvokeMulti();
}
// operator _contrib_ssd_scan — inputs: data, dt, A, B, C, D
inline NDArray _contrib_ssd_scan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_contrib_ssd_scan", inputs, attrs).Invoke();
}
// operator _copy
inline NDArray _copy(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_copy", inputs, attrs).Invoke();
}
// operator _crop_assign
inline NDArray _crop_assign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_crop_assign", inputs, attrs).Invoke();
}
// operator _crop_assign_scalar
inline NDArray _crop_assign_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_crop_assign_scalar", inputs, attrs).Invoke();
}
// operator _div_scalar
inline NDArray _div_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_div_scalar", inputs, attrs).Invoke();
}
// operator _equal
inline NDArray _equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_equal", inputs, attrs).Invoke();
}
// operator _equal_scalar
inline NDArray _equal_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_equal_scalar", inputs, attrs).Invoke();
}
// operator _eye
inline NDArray _eye(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_eye", inputs, attrs).Invoke();
}
// operator _foreach
inline std::vector<NDArray> _foreach(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_foreach", inputs, attrs).InvokeMulti();
}
// operator _full
inline NDArray _full(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_full", inputs, attrs).Invoke();
}
// operator _grad_add
inline NDArray _grad_add(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_grad_add", inputs, attrs).Invoke();
}
// operator _greater
inline NDArray _greater(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_greater", inputs, attrs).Invoke();
}
// operator _greater_equal
inline NDArray _greater_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_greater_equal", inputs, attrs).Invoke();
}
// operator _greater_equal_scalar
inline NDArray _greater_equal_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_greater_equal_scalar", inputs, attrs).Invoke();
}
// operator _greater_scalar
inline NDArray _greater_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_greater_scalar", inputs, attrs).Invoke();
}
// operator _hypot
inline NDArray _hypot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_hypot", inputs, attrs).Invoke();
}
// operator _hypot_scalar
inline NDArray _hypot_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_hypot_scalar", inputs, attrs).Invoke();
}
// operator _identity_with_attr_like_rhs
inline NDArray _identity_with_attr_like_rhs(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_identity_with_attr_like_rhs", inputs, attrs).Invoke();
}
// operator _image_adjust_lighting
inline NDArray _image_adjust_lighting(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_adjust_lighting", inputs, attrs).Invoke();
}
// operator _image_crop
inline NDArray _image_crop(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_crop", inputs, attrs).Invoke();
}
// operator _image_flip_left_right
inline NDArray _image_flip_left_right(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_flip_left_right", inputs, attrs).Invoke();
}
// operator _image_flip_top_bottom
inline NDArray _image_flip_top_bottom(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_flip_top_bottom", inputs, attrs).Invoke();
}
// operator _image_normalize
inline NDArray _image_normalize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_normalize", inputs, attrs).Invoke();
}
// operator _image_random_brightness
inline NDArray _image_random_brightness(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_brightness", inputs, attrs).Invoke();
}
// operator _image_random_color_jitter
inline NDArray _image_random_color_jitter(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_color_jitter", inputs, attrs).Invoke();
}
// operator _image_random_contrast
inline NDArray _image_random_contrast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_contrast", inputs, attrs).Invoke();
}
// operator _image_random_flip_left_right
inline NDArray _image_random_flip_left_right(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_flip_left_right", inputs, attrs).Invoke();
}
// operator _image_random_flip_top_bottom
inline NDArray _image_random_flip_top_bottom(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_flip_top_bottom", inputs, attrs).Invoke();
}
// operator _image_random_hue
inline NDArray _image_random_hue(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_hue", inputs, attrs).Invoke();
}
// operator _image_random_lighting
inline NDArray _image_random_lighting(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_lighting", inputs, attrs).Invoke();
}
// operator _image_random_saturation
inline NDArray _image_random_saturation(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_random_saturation", inputs, attrs).Invoke();
}
// operator _image_resize
inline NDArray _image_resize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_resize", inputs, attrs).Invoke();
}
// operator _image_to_tensor
inline NDArray _image_to_tensor(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_image_to_tensor", inputs, attrs).Invoke();
}
// operator _lesser
inline NDArray _lesser(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_lesser", inputs, attrs).Invoke();
}
// operator _lesser_equal
inline NDArray _lesser_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_lesser_equal", inputs, attrs).Invoke();
}
// operator _lesser_equal_scalar
inline NDArray _lesser_equal_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_lesser_equal_scalar", inputs, attrs).Invoke();
}
// operator _lesser_scalar
inline NDArray _lesser_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_lesser_scalar", inputs, attrs).Invoke();
}
// operator _linalg_det
inline NDArray _linalg_det(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_det", inputs, attrs).Invoke();
}
// operator _linalg_extractdiag
inline NDArray _linalg_extractdiag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_extractdiag", inputs, attrs).Invoke();
}
// operator _linalg_extracttrian
inline NDArray _linalg_extracttrian(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_extracttrian", inputs, attrs).Invoke();
}
// operator _linalg_gelqf
inline std::vector<NDArray> _linalg_gelqf(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_gelqf", inputs, attrs).InvokeMulti();
}
// operator _linalg_gemm
inline NDArray _linalg_gemm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_gemm", inputs, attrs).Invoke();
}
// operator _linalg_gemm2
inline NDArray _linalg_gemm2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_gemm2", inputs, attrs).Invoke();
}
// operator _linalg_inverse
inline NDArray _linalg_inverse(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_inverse", inputs, attrs).Invoke();
}
// operator _linalg_makediag
inline NDArray _linalg_makediag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_makediag", inputs, attrs).Invoke();
}
// operator _linalg_maketrian
inline NDArray _linalg_maketrian(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_maketrian", inputs, attrs).Invoke();
}
// operator _linalg_potrf
inline NDArray _linalg_potrf(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_potrf", inputs, attrs).Invoke();
}
// operator _linalg_potri
inline NDArray _linalg_potri(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_potri", inputs, attrs).Invoke();
}
// operator _linalg_slogdet
inline std::vector<NDArray> _linalg_slogdet(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_slogdet", inputs, attrs).InvokeMulti();
}
// operator _linalg_sumlogdiag
inline NDArray _linalg_sumlogdiag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_sumlogdiag", inputs, attrs).Invoke();
}
// operator _linalg_syevd
inline std::vector<NDArray> _linalg_syevd(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_syevd", inputs, attrs).InvokeMulti();
}
// operator _linalg_syrk
inline NDArray _linalg_syrk(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_syrk", inputs, attrs).Invoke();
}
// operator _linalg_trmm
inline NDArray _linalg_trmm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_trmm", inputs, attrs).Invoke();
}
// operator _linalg_trsm
inline NDArray _linalg_trsm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linalg_trsm", inputs, attrs).Invoke();
}
// operator _linspace
inline NDArray _linspace(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_linspace", inputs, attrs).Invoke();
}
// operator _logical_and
inline NDArray _logical_and(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_and", inputs, attrs).Invoke();
}
// operator _logical_and_scalar
inline NDArray _logical_and_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_and_scalar", inputs, attrs).Invoke();
}
// operator _logical_or
inline NDArray _logical_or(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_or", inputs, attrs).Invoke();
}
// operator _logical_or_scalar
inline NDArray _logical_or_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_or_scalar", inputs, attrs).Invoke();
}
// operator _logical_xor
inline NDArray _logical_xor(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_xor", inputs, attrs).Invoke();
}
// operator _logical_xor_scalar
inline NDArray _logical_xor_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_logical_xor_scalar", inputs, attrs).Invoke();
}
// operator _maximum
inline NDArray _maximum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_maximum", inputs, attrs).Invoke();
}
// operator _maximum_scalar
inline NDArray _maximum_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_maximum_scalar", inputs, attrs).Invoke();
}
// operator _minimum
inline NDArray _minimum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_minimum", inputs, attrs).Invoke();
}
// operator _minimum_scalar
inline NDArray _minimum_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_minimum_scalar", inputs, attrs).Invoke();
}
// operator _minus_scalar
inline NDArray _minus_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_minus_scalar", inputs, attrs).Invoke();
}
// operator _mod
inline NDArray _mod(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_mod", inputs, attrs).Invoke();
}
// operator _mod_scalar
inline NDArray _mod_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_mod_scalar", inputs, attrs).Invoke();
}
// operator _mp_adamw_update
inline std::vector<NDArray> _mp_adamw_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_mp_adamw_update", inputs, attrs).InvokeMulti();
}
// operator _mul_scalar
inline NDArray _mul_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_mul_scalar", inputs, attrs).Invoke();
}
// operator _not_equal
inline NDArray _not_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_not_equal", inputs, attrs).Invoke();
}
// operator _not_equal_scalar
inline NDArray _not_equal_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_not_equal_scalar", inputs, attrs).Invoke();
}
// operator _ones
inline NDArray _ones(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_ones", inputs, attrs).Invoke();
}
// operator _plus_scalar
inline NDArray _plus_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_plus_scalar", inputs, attrs).Invoke();
}
// operator _power
inline NDArray _power(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_power", inputs, attrs).Invoke();
}
// operator _power_scalar
inline NDArray _power_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_power_scalar", inputs, attrs).Invoke();
}
// operator _random_bernoulli
inline NDArray _random_bernoulli(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_bernoulli", inputs, attrs).Invoke();
}
// operator _random_exponential
inline NDArray _random_exponential(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_exponential", inputs, attrs).Invoke();
}
// operator _random_exponential_like
inline NDArray _random_exponential_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_exponential_like", inputs, attrs).Invoke();
}
// operator _random_gamma
inline NDArray _random_gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_gamma", inputs, attrs).Invoke();
}
// operator _random_gamma_like
inline NDArray _random_gamma_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_gamma_like", inputs, attrs).Invoke();
}
// operator _random_generalized_negative_binomial
inline NDArray _random_generalized_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_generalized_negative_binomial", inputs, attrs).Invoke();
}
// operator _random_generalized_negative_binomial_like
inline NDArray _random_generalized_negative_binomial_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_generalized_negative_binomial_like", inputs, attrs).Invoke();
}
// operator _random_negative_binomial
inline NDArray _random_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_negative_binomial", inputs, attrs).Invoke();
}
// operator _random_negative_binomial_like
inline NDArray _random_negative_binomial_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_negative_binomial_like", inputs, attrs).Invoke();
}
// operator _random_normal
inline NDArray _random_normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_normal", inputs, attrs).Invoke();
}
// operator _random_normal_like
inline NDArray _random_normal_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_normal_like", inputs, attrs).Invoke();
}
// operator _random_pdf_dirichlet
inline NDArray _random_pdf_dirichlet(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_dirichlet", inputs, attrs).Invoke();
}
// operator _random_pdf_exponential
inline NDArray _random_pdf_exponential(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_exponential", inputs, attrs).Invoke();
}
// operator _random_pdf_gamma
inline NDArray _random_pdf_gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_gamma", inputs, attrs).Invoke();
}
// operator _random_pdf_generalized_negative_binomial
inline NDArray _random_pdf_generalized_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_generalized_negative_binomial", inputs, attrs).Invoke();
}
// operator _random_pdf_negative_binomial
inline NDArray _random_pdf_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_negative_binomial", inputs, attrs).Invoke();
}
// operator _random_pdf_normal
inline NDArray _random_pdf_normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_normal", inputs, attrs).Invoke();
}
// operator _random_pdf_poisson
inline NDArray _random_pdf_poisson(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_poisson", inputs, attrs).Invoke();
}
// operator _random_pdf_uniform
inline NDArray _random_pdf_uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_pdf_uniform", inputs, attrs).Invoke();
}
// operator _random_poisson
inline NDArray _random_poisson(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_poisson", inputs, attrs).Invoke();
}
// operator _random_poisson_like
inline NDArray _random_poisson_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_poisson_like", inputs, attrs).Invoke();
}
// operator _random_randint
inline NDArray _random_randint(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_randint", inputs, attrs).Invoke();
}
// operator _random_uniform
inline NDArray _random_uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_uniform", inputs, attrs).Invoke();
}
// operator _random_uniform_like
inline NDArray _random_uniform_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_random_uniform_like", inputs, attrs).Invoke();
}
// operator _ravel_multi_index
inline NDArray _ravel_multi_index(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_ravel_multi_index", inputs, attrs).Invoke();
}
// operator _rdiv_scalar
inline NDArray _rdiv_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_rdiv_scalar", inputs, attrs).Invoke();
}
// operator _rminus_scalar
inline NDArray _rminus_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_rminus_scalar", inputs, attrs).Invoke();
}
// operator _rmod_scalar
inline NDArray _rmod_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_rmod_scalar", inputs, attrs).Invoke();
}
// operator _rnn_param_concat
inline NDArray _rnn_param_concat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_rnn_param_concat", inputs, attrs).Invoke();
}
// operator _rpower_scalar
inline NDArray _rpower_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_rpower_scalar", inputs, attrs).Invoke();
}
// operator _sample_exponential
inline NDArray _sample_exponential(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_exponential", inputs, attrs).Invoke();
}
// operator _sample_gamma
inline NDArray _sample_gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_gamma", inputs, attrs).Invoke();
}
// operator _sample_generalized_negative_binomial
inline NDArray _sample_generalized_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_generalized_negative_binomial", inputs, attrs).Invoke();
}
// operator _sample_multinomial
inline NDArray _sample_multinomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_multinomial", inputs, attrs).Invoke();
}
// operator _sample_negative_binomial
inline NDArray _sample_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_negative_binomial", inputs, attrs).Invoke();
}
// operator _sample_normal
inline NDArray _sample_normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_normal", inputs, attrs).Invoke();
}
// operator _sample_poisson
inline NDArray _sample_poisson(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_poisson", inputs, attrs).Invoke();
}
// operator _sample_uniform
inline NDArray _sample_uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_uniform", inputs, attrs).Invoke();
}
// operator _sample_unique_zipfian
inline NDArray _sample_unique_zipfian(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sample_unique_zipfian", inputs, attrs).Invoke();
}
// operator _scatter_elemwise_div
inline NDArray _scatter_elemwise_div(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_scatter_elemwise_div", inputs, attrs).Invoke();
}
// operator _scatter_minus_scalar
inline NDArray _scatter_minus_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_scatter_minus_scalar", inputs, attrs).Invoke();
}
// operator _scatter_plus_scalar
inline NDArray _scatter_plus_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_scatter_plus_scalar", inputs, attrs).Invoke();
}
// operator _scatter_set_nd
inline NDArray _scatter_set_nd(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_scatter_set_nd", inputs, attrs).Invoke();
}
// operator _shuffle
inline NDArray _shuffle(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_shuffle", inputs, attrs).Invoke();
}
// operator _slice_assign
inline NDArray _slice_assign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_slice_assign", inputs, attrs).Invoke();
}
// operator _slice_assign_scalar
inline NDArray _slice_assign_scalar(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_slice_assign_scalar", inputs, attrs).Invoke();
}
// operator _sparse_adagrad_update
inline std::vector<NDArray> _sparse_adagrad_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sparse_adagrad_update", inputs, attrs).InvokeMulti();
}
// operator _sparse_retain
inline NDArray _sparse_retain(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_sparse_retain", inputs, attrs).Invoke();
}
// operator _split_v2
inline std::vector<NDArray> _split_v2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_split_v2", inputs, attrs).InvokeMulti();
}
// operator _square_sum
inline NDArray _square_sum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_square_sum", inputs, attrs).Invoke();
}
// operator _unravel_index
inline NDArray _unravel_index(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_unravel_index", inputs, attrs).Invoke();
}
// operator _while_loop
inline std::vector<NDArray> _while_loop(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_while_loop", inputs, attrs).InvokeMulti();
}
// operator _zeros
inline NDArray _zeros(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_zeros", inputs, attrs).Invoke();
}
// operator _zeros_without_dtype
inline NDArray _zeros_without_dtype(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("_zeros_without_dtype", inputs, attrs).Invoke();
}
// operator abs
inline NDArray abs(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("abs", inputs, attrs).Invoke();
}
// operator adam_update
inline std::vector<NDArray> adam_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("adam_update", inputs, attrs).InvokeMulti();
}
// operator adamw_update
inline std::vector<NDArray> adamw_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("adamw_update", inputs, attrs).InvokeMulti();
}
// operator add_n
inline NDArray add_n(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("add_n", inputs, attrs).Invoke();
}
// operator all_finite
inline NDArray all_finite(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("all_finite", inputs, attrs).Invoke();
}
// operator amp_cast
inline NDArray amp_cast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("amp_cast", inputs, attrs).Invoke();
}
// operator amp_multicast
inline std::vector<NDArray> amp_multicast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("amp_multicast", inputs, attrs).InvokeMulti();
}
// operator arange_like
inline NDArray arange_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arange_like", inputs, attrs).Invoke();
}
// operator arccos
inline NDArray arccos(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arccos", inputs, attrs).Invoke();
}
// operator arccosh
inline NDArray arccosh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arccosh", inputs, attrs).Invoke();
}
// operator arcsin
inline NDArray arcsin(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arcsin", inputs, attrs).Invoke();
}
// operator arcsinh
inline NDArray arcsinh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arcsinh", inputs, attrs).Invoke();
}
// operator arctan
inline NDArray arctan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arctan", inputs, attrs).Invoke();
}
// operator arctanh
inline NDArray arctanh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("arctanh", inputs, attrs).Invoke();
}
// operator argmax
inline NDArray argmax(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("argmax", inputs, attrs).Invoke();
}
// operator argmax_channel
inline NDArray argmax_channel(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("argmax_channel", inputs, attrs).Invoke();
}
// operator argmin
inline NDArray argmin(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("argmin", inputs, attrs).Invoke();
}
// operator argsort
inline NDArray argsort(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("argsort", inputs, attrs).Invoke();
}
// operator batch_dot
inline NDArray batch_dot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("batch_dot", inputs, attrs).Invoke();
}
// operator batch_take
inline NDArray batch_take(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("batch_take", inputs, attrs).Invoke();
}
// operator bipartite_matching
inline std::vector<NDArray> bipartite_matching(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("bipartite_matching", inputs, attrs).InvokeMulti();
}
// operator boolean_mask
inline NDArray boolean_mask(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("boolean_mask", inputs, attrs).Invoke();
}
// operator boolean_mask_fill
inline NDArray boolean_mask_fill(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("boolean_mask_fill", inputs, attrs).Invoke();
}
// operator box_iou
inline NDArray box_iou(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("box_iou", inputs, attrs).Invoke();
}
// operator box_nms
inline std::vector<NDArray> box_nms(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("box_nms", inputs, attrs).InvokeMulti();
}
// operator broadcast_add
inline NDArray broadcast_add(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_add", inputs, attrs).Invoke();
}
// operator broadcast_axes
inline NDArray broadcast_axes(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_axes", inputs, attrs).Invoke();
}
// operator broadcast_axis
inline NDArray broadcast_axis(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_axis", inputs, attrs).Invoke();
}
// operator broadcast_div
inline NDArray broadcast_div(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_div", inputs, attrs).Invoke();
}
// operator broadcast_equal
inline NDArray broadcast_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_equal", inputs, attrs).Invoke();
}
// operator broadcast_greater
inline NDArray broadcast_greater(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_greater", inputs, attrs).Invoke();
}
// operator broadcast_greater_equal
inline NDArray broadcast_greater_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_greater_equal", inputs, attrs).Invoke();
}
// operator broadcast_hypot
inline NDArray broadcast_hypot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_hypot", inputs, attrs).Invoke();
}
// operator broadcast_lesser
inline NDArray broadcast_lesser(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_lesser", inputs, attrs).Invoke();
}
// operator broadcast_lesser_equal
inline NDArray broadcast_lesser_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_lesser_equal", inputs, attrs).Invoke();
}
// operator broadcast_like
inline NDArray broadcast_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_like", inputs, attrs).Invoke();
}
// operator broadcast_logical_and
inline NDArray broadcast_logical_and(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_logical_and", inputs, attrs).Invoke();
}
// operator broadcast_logical_or
inline NDArray broadcast_logical_or(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_logical_or", inputs, attrs).Invoke();
}
// operator broadcast_logical_xor
inline NDArray broadcast_logical_xor(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_logical_xor", inputs, attrs).Invoke();
}
// operator broadcast_maximum
inline NDArray broadcast_maximum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_maximum", inputs, attrs).Invoke();
}
// operator broadcast_minimum
inline NDArray broadcast_minimum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_minimum", inputs, attrs).Invoke();
}
// operator broadcast_mod
inline NDArray broadcast_mod(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_mod", inputs, attrs).Invoke();
}
// operator broadcast_mul
inline NDArray broadcast_mul(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_mul", inputs, attrs).Invoke();
}
// operator broadcast_not_equal
inline NDArray broadcast_not_equal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_not_equal", inputs, attrs).Invoke();
}
// operator broadcast_power
inline NDArray broadcast_power(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_power", inputs, attrs).Invoke();
}
// operator broadcast_sub
inline NDArray broadcast_sub(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_sub", inputs, attrs).Invoke();
}
// operator broadcast_to
inline NDArray broadcast_to(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("broadcast_to", inputs, attrs).Invoke();
}
// operator cast
inline NDArray cast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("cast", inputs, attrs).Invoke();
}
// operator cast_storage
inline NDArray cast_storage(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("cast_storage", inputs, attrs).Invoke();
}
// operator causal_conv1d — inputs: data, weight, bias
inline NDArray causal_conv1d(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("causal_conv1d", inputs, attrs).Invoke();
}
// operator cbrt
inline NDArray cbrt(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("cbrt", inputs, attrs).Invoke();
}
// operator ceil
inline NDArray ceil(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ceil", inputs, attrs).Invoke();
}
// operator clip
inline NDArray clip(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("clip", inputs, attrs).Invoke();
}
// operator concat
inline NDArray concat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("concat", inputs, attrs).Invoke();
}
// operator cos
inline NDArray cos(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("cos", inputs, attrs).Invoke();
}
// operator cosh
inline NDArray cosh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("cosh", inputs, attrs).Invoke();
}
// operator crop
inline NDArray crop(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("crop", inputs, attrs).Invoke();
}
// operator ctc_loss
inline NDArray ctc_loss(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ctc_loss", inputs, attrs).Invoke();
}
// operator degrees
inline NDArray degrees(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("degrees", inputs, attrs).Invoke();
}
// operator depth_to_space
inline NDArray depth_to_space(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("depth_to_space", inputs, attrs).Invoke();
}
// operator dequantize
inline NDArray dequantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("dequantize", inputs, attrs).Invoke();
}
// operator det
inline NDArray det(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("det", inputs, attrs).Invoke();
}
// operator diag
inline NDArray diag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("diag", inputs, attrs).Invoke();
}
// operator div_sqrt_dim
inline NDArray div_sqrt_dim(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("div_sqrt_dim", inputs, attrs).Invoke();
}
// operator dot
inline NDArray dot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("dot", inputs, attrs).Invoke();
}
// operator dot_product
inline NDArray dot_product(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("dot_product", inputs, attrs).Invoke();
}
// operator elemwise_add
inline NDArray elemwise_add(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_add", inputs, attrs).Invoke();
}
// operator elemwise_div
inline NDArray elemwise_div(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_div", inputs, attrs).Invoke();
}
// operator elemwise_hypot
inline NDArray elemwise_hypot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_hypot", inputs, attrs).Invoke();
}
// operator elemwise_maximum
inline NDArray elemwise_maximum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_maximum", inputs, attrs).Invoke();
}
// operator elemwise_minimum
inline NDArray elemwise_minimum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_minimum", inputs, attrs).Invoke();
}
// operator elemwise_mod
inline NDArray elemwise_mod(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_mod", inputs, attrs).Invoke();
}
// operator elemwise_mul
inline NDArray elemwise_mul(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_mul", inputs, attrs).Invoke();
}
// operator elemwise_power
inline NDArray elemwise_power(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_power", inputs, attrs).Invoke();
}
// operator elemwise_sub
inline NDArray elemwise_sub(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_sub", inputs, attrs).Invoke();
}
// operator elemwise_sum
inline NDArray elemwise_sum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("elemwise_sum", inputs, attrs).Invoke();
}
// operator erf
inline NDArray erf(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("erf", inputs, attrs).Invoke();
}
// operator erfinv
inline NDArray erfinv(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("erfinv", inputs, attrs).Invoke();
}
// operator exp
inline NDArray exp(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("exp", inputs, attrs).Invoke();
}
// operator expand_dims
inline NDArray expand_dims(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("expand_dims", inputs, attrs).Invoke();
}
// operator expm1
inline NDArray expm1(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("expm1", inputs, attrs).Invoke();
}
// operator fft
inline NDArray fft(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("fft", inputs, attrs).Invoke();
}
// operator fix
inline NDArray fix(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("fix", inputs, attrs).Invoke();
}
// operator flash_attention
inline NDArray flash_attention(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("flash_attention", inputs, attrs).Invoke();
}
// operator flatten
inline NDArray flatten(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("flatten", inputs, attrs).Invoke();
}
// operator flip
inline NDArray flip(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("flip", inputs, attrs).Invoke();
}
// operator floor
inline NDArray floor(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("floor", inputs, attrs).Invoke();
}
// operator ftml_update
inline std::vector<NDArray> ftml_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ftml_update", inputs, attrs).InvokeMulti();
}
// operator ftrl_update
inline std::vector<NDArray> ftrl_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ftrl_update", inputs, attrs).InvokeMulti();
}
// operator gamma
inline NDArray gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("gamma", inputs, attrs).Invoke();
}
// operator gammaln
inline NDArray gammaln(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("gammaln", inputs, attrs).Invoke();
}
// operator gather_nd
inline NDArray gather_nd(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("gather_nd", inputs, attrs).Invoke();
}
// operator gradientmultiplier
inline NDArray gradientmultiplier(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("gradientmultiplier", inputs, attrs).Invoke();
}
// operator hard_sigmoid
inline NDArray hard_sigmoid(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("hard_sigmoid", inputs, attrs).Invoke();
}
// operator histogram
inline std::vector<NDArray> histogram(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("histogram", inputs, attrs).InvokeMulti();
}
// operator hypot
inline NDArray hypot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("hypot", inputs, attrs).Invoke();
}
// operator identity
inline NDArray identity(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("identity", inputs, attrs).Invoke();
}
// operator ifft
inline NDArray ifft(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ifft", inputs, attrs).Invoke();
}
// operator image_adjust_lighting
inline NDArray image_adjust_lighting(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_adjust_lighting", inputs, attrs).Invoke();
}
// operator image_crop
inline NDArray image_crop(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_crop", inputs, attrs).Invoke();
}
// operator image_flip_left_right
inline NDArray image_flip_left_right(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_flip_left_right", inputs, attrs).Invoke();
}
// operator image_flip_top_bottom
inline NDArray image_flip_top_bottom(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_flip_top_bottom", inputs, attrs).Invoke();
}
// operator image_normalize
inline NDArray image_normalize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_normalize", inputs, attrs).Invoke();
}
// operator image_random_brightness
inline NDArray image_random_brightness(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_brightness", inputs, attrs).Invoke();
}
// operator image_random_color_jitter
inline NDArray image_random_color_jitter(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_color_jitter", inputs, attrs).Invoke();
}
// operator image_random_contrast
inline NDArray image_random_contrast(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_contrast", inputs, attrs).Invoke();
}
// operator image_random_flip_left_right
inline NDArray image_random_flip_left_right(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_flip_left_right", inputs, attrs).Invoke();
}
// operator image_random_flip_top_bottom
inline NDArray image_random_flip_top_bottom(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_flip_top_bottom", inputs, attrs).Invoke();
}
// operator image_random_hue
inline NDArray image_random_hue(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_hue", inputs, attrs).Invoke();
}
// operator image_random_lighting
inline NDArray image_random_lighting(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_lighting", inputs, attrs).Invoke();
}
// operator image_random_saturation
inline NDArray image_random_saturation(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_random_saturation", inputs, attrs).Invoke();
}
// operator image_resize
inline NDArray image_resize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_resize", inputs, attrs).Invoke();
}
// operator image_to_tensor
inline NDArray image_to_tensor(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("image_to_tensor", inputs, attrs).Invoke();
}
// operator inverse
inline NDArray inverse(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("inverse", inputs, attrs).Invoke();
}
// operator kda_scan — inputs: q, k, v, g, beta
inline NDArray kda_scan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("kda_scan", inputs, attrs).Invoke();
}
// operator khatri_rao
inline NDArray khatri_rao(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("khatri_rao", inputs, attrs).Invoke();
}
// operator lamb_update_phase1
inline std::vector<NDArray> lamb_update_phase1(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("lamb_update_phase1", inputs, attrs).InvokeMulti();
}
// operator linalg_det
inline NDArray linalg_det(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_det", inputs, attrs).Invoke();
}
// operator linalg_extractdiag
inline NDArray linalg_extractdiag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_extractdiag", inputs, attrs).Invoke();
}
// operator linalg_extracttrian
inline NDArray linalg_extracttrian(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_extracttrian", inputs, attrs).Invoke();
}
// operator linalg_gelqf
inline std::vector<NDArray> linalg_gelqf(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_gelqf", inputs, attrs).InvokeMulti();
}
// operator linalg_gemm
inline NDArray linalg_gemm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_gemm", inputs, attrs).Invoke();
}
// operator linalg_gemm2
inline NDArray linalg_gemm2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_gemm2", inputs, attrs).Invoke();
}
// operator linalg_inverse
inline NDArray linalg_inverse(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_inverse", inputs, attrs).Invoke();
}
// operator linalg_makediag
inline NDArray linalg_makediag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_makediag", inputs, attrs).Invoke();
}
// operator linalg_maketrian
inline NDArray linalg_maketrian(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_maketrian", inputs, attrs).Invoke();
}
// operator linalg_potrf
inline NDArray linalg_potrf(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_potrf", inputs, attrs).Invoke();
}
// operator linalg_potri
inline NDArray linalg_potri(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_potri", inputs, attrs).Invoke();
}
// operator linalg_slogdet
inline std::vector<NDArray> linalg_slogdet(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_slogdet", inputs, attrs).InvokeMulti();
}
// operator linalg_sumlogdiag
inline NDArray linalg_sumlogdiag(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_sumlogdiag", inputs, attrs).Invoke();
}
// operator linalg_syevd
inline std::vector<NDArray> linalg_syevd(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_syevd", inputs, attrs).InvokeMulti();
}
// operator linalg_syrk
inline NDArray linalg_syrk(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_syrk", inputs, attrs).Invoke();
}
// operator linalg_trmm
inline NDArray linalg_trmm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_trmm", inputs, attrs).Invoke();
}
// operator linalg_trsm
inline NDArray linalg_trsm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("linalg_trsm", inputs, attrs).Invoke();
}
// operator log
inline NDArray log(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("log", inputs, attrs).Invoke();
}
// operator log10
inline NDArray log10(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("log10", inputs, attrs).Invoke();
}
// operator log1p
inline NDArray log1p(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("log1p", inputs, attrs).Invoke();
}
// operator log2
inline NDArray log2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("log2", inputs, attrs).Invoke();
}
// operator log_softmax
inline NDArray log_softmax(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("log_softmax", inputs, attrs).Invoke();
}
// operator logical_not
inline NDArray logical_not(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("logical_not", inputs, attrs).Invoke();
}
// operator make_loss
inline NDArray make_loss(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("make_loss", inputs, attrs).Invoke();
}
// operator max
inline NDArray max(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("max", inputs, attrs).Invoke();
}
// operator maximum
inline NDArray maximum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("maximum", inputs, attrs).Invoke();
}
// operator mean
inline NDArray mean(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("mean", inputs, attrs).Invoke();
}
// operator min
inline NDArray min(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("min", inputs, attrs).Invoke();
}
// operator minimum
inline NDArray minimum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("minimum", inputs, attrs).Invoke();
}
// operator moments
inline std::vector<NDArray> moments(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("moments", inputs, attrs).InvokeMulti();
}
// operator mp_adamw_update
inline std::vector<NDArray> mp_adamw_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("mp_adamw_update", inputs, attrs).InvokeMulti();
}
// operator mp_nag_mom_update
inline std::vector<NDArray> mp_nag_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("mp_nag_mom_update", inputs, attrs).InvokeMulti();
}
// operator mp_sgd_mom_update
inline std::vector<NDArray> mp_sgd_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("mp_sgd_mom_update", inputs, attrs).InvokeMulti();
}
// operator mp_sgd_update
inline std::vector<NDArray> mp_sgd_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("mp_sgd_update", inputs, attrs).InvokeMulti();
}
// operator multi_all_finite
inline NDArray multi_all_finite(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multi_all_finite", inputs, attrs).Invoke();
}
// operator multi_mp_sgd_mom_update
inline std::vector<NDArray> multi_mp_sgd_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multi_mp_sgd_mom_update", inputs, attrs).InvokeMulti();
}
// operator multi_mp_sgd_update
inline std::vector<NDArray> multi_mp_sgd_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multi_mp_sgd_update", inputs, attrs).InvokeMulti();
}
// operator multi_sgd_mom_update
inline std::vector<NDArray> multi_sgd_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multi_sgd_mom_update", inputs, attrs).InvokeMulti();
}
// operator multi_sgd_update
inline std::vector<NDArray> multi_sgd_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multi_sgd_update", inputs, attrs).InvokeMulti();
}
// operator multinomial
inline NDArray multinomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("multinomial", inputs, attrs).Invoke();
}
// operator nag_mom_update
inline std::vector<NDArray> nag_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("nag_mom_update", inputs, attrs).InvokeMulti();
}
// operator nanprod
inline NDArray nanprod(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("nanprod", inputs, attrs).Invoke();
}
// operator nansum
inline NDArray nansum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("nansum", inputs, attrs).Invoke();
}
// operator negative
inline NDArray negative(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("negative", inputs, attrs).Invoke();
}
// operator norm
inline NDArray norm(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("norm", inputs, attrs).Invoke();
}
// operator normal
inline NDArray normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("normal", inputs, attrs).Invoke();
}
// operator one_hot
inline NDArray one_hot(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("one_hot", inputs, attrs).Invoke();
}
// operator ones_like
inline NDArray ones_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ones_like", inputs, attrs).Invoke();
}
// operator pad
inline NDArray pad(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("pad", inputs, attrs).Invoke();
}
// operator pick
inline NDArray pick(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("pick", inputs, attrs).Invoke();
}
// operator prod
inline NDArray prod(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("prod", inputs, attrs).Invoke();
}
// operator quantize
inline std::vector<NDArray> quantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantize", inputs, attrs).InvokeMulti();
}
// operator quantize_v2
inline std::vector<NDArray> quantize_v2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantize_v2", inputs, attrs).InvokeMulti();
}
// operator quantized_act
inline std::vector<NDArray> quantized_act(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_act", inputs, attrs).InvokeMulti();
}
// operator quantized_concat
inline std::vector<NDArray> quantized_concat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_concat", inputs, attrs).InvokeMulti();
}
// operator quantized_conv
inline std::vector<NDArray> quantized_conv(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_conv", inputs, attrs).InvokeMulti();
}
// operator quantized_elemwise_add
inline std::vector<NDArray> quantized_elemwise_add(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_elemwise_add", inputs, attrs).InvokeMulti();
}
// operator quantized_flatten
inline std::vector<NDArray> quantized_flatten(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_flatten", inputs, attrs).InvokeMulti();
}
// operator quantized_fully_connected
inline std::vector<NDArray> quantized_fully_connected(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_fully_connected", inputs, attrs).InvokeMulti();
}
// operator quantized_pooling
inline std::vector<NDArray> quantized_pooling(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("quantized_pooling", inputs, attrs).InvokeMulti();
}
// operator radians
inline NDArray radians(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("radians", inputs, attrs).Invoke();
}
// operator random_pdf_dirichlet
inline NDArray random_pdf_dirichlet(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_dirichlet", inputs, attrs).Invoke();
}
// operator random_pdf_exponential
inline NDArray random_pdf_exponential(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_exponential", inputs, attrs).Invoke();
}
// operator random_pdf_gamma
inline NDArray random_pdf_gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_gamma", inputs, attrs).Invoke();
}
// operator random_pdf_generalized_negative_binomial
inline NDArray random_pdf_generalized_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_generalized_negative_binomial", inputs, attrs).Invoke();
}
// operator random_pdf_negative_binomial
inline NDArray random_pdf_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_negative_binomial", inputs, attrs).Invoke();
}
// operator random_pdf_normal
inline NDArray random_pdf_normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_normal", inputs, attrs).Invoke();
}
// operator random_pdf_poisson
inline NDArray random_pdf_poisson(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_poisson", inputs, attrs).Invoke();
}
// operator random_pdf_uniform
inline NDArray random_pdf_uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("random_pdf_uniform", inputs, attrs).Invoke();
}
// operator ravel_multi_index
inline NDArray ravel_multi_index(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ravel_multi_index", inputs, attrs).Invoke();
}
// operator rcbrt
inline NDArray rcbrt(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rcbrt", inputs, attrs).Invoke();
}
// operator reciprocal
inline NDArray reciprocal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("reciprocal", inputs, attrs).Invoke();
}
// operator relu
inline NDArray relu(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("relu", inputs, attrs).Invoke();
}
// operator repeat
inline NDArray repeat(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("repeat", inputs, attrs).Invoke();
}
// operator requantize
inline std::vector<NDArray> requantize(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("requantize", inputs, attrs).InvokeMulti();
}
// operator reshape
inline NDArray reshape(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("reshape", inputs, attrs).Invoke();
}
// operator reshape_like
inline NDArray reshape_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("reshape_like", inputs, attrs).Invoke();
}
// operator reverse
inline NDArray reverse(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("reverse", inputs, attrs).Invoke();
}
// operator rint
inline NDArray rint(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rint", inputs, attrs).Invoke();
}
// operator rmsprop_update
inline std::vector<NDArray> rmsprop_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rmsprop_update", inputs, attrs).InvokeMulti();
}
// operator rmspropalex_update
inline std::vector<NDArray> rmspropalex_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rmspropalex_update", inputs, attrs).InvokeMulti();
}
// operator rotary_embedding — inputs: data, positions
inline NDArray rotary_embedding(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rotary_embedding", inputs, attrs).Invoke();
}
// operator round
inline NDArray round(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("round", inputs, attrs).Invoke();
}
// operator routed_experts — inputs: data, router_weight, w1, w3, w2
inline std::vector<NDArray> routed_experts(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("routed_experts", inputs, attrs).InvokeMulti();
}
// operator rsqrt
inline NDArray rsqrt(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("rsqrt", inputs, attrs).Invoke();
}
// operator sample_exponential
inline NDArray sample_exponential(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_exponential", inputs, attrs).Invoke();
}
// operator sample_gamma
inline NDArray sample_gamma(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_gamma", inputs, attrs).Invoke();
}
// operator sample_generalized_negative_binomial
inline NDArray sample_generalized_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_generalized_negative_binomial", inputs, attrs).Invoke();
}
// operator sample_negative_binomial
inline NDArray sample_negative_binomial(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_negative_binomial", inputs, attrs).Invoke();
}
// operator sample_normal
inline NDArray sample_normal(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_normal", inputs, attrs).Invoke();
}
// operator sample_poisson
inline NDArray sample_poisson(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_poisson", inputs, attrs).Invoke();
}
// operator sample_uniform
inline NDArray sample_uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sample_uniform", inputs, attrs).Invoke();
}
// operator scatter_nd
inline NDArray scatter_nd(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("scatter_nd", inputs, attrs).Invoke();
}
// operator sgd_mom_update
inline std::vector<NDArray> sgd_mom_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sgd_mom_update", inputs, attrs).InvokeMulti();
}
// operator sgd_update
inline NDArray sgd_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sgd_update", inputs, attrs).Invoke();
}
// operator shape_array
inline NDArray shape_array(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("shape_array", inputs, attrs).Invoke();
}
// operator shuffle
inline NDArray shuffle(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("shuffle", inputs, attrs).Invoke();
}
// operator sigmoid
inline NDArray sigmoid(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sigmoid", inputs, attrs).Invoke();
}
// operator sign
inline NDArray sign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sign", inputs, attrs).Invoke();
}
// operator signsgd_update
inline NDArray signsgd_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("signsgd_update", inputs, attrs).Invoke();
}
// operator signum_update
inline std::vector<NDArray> signum_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("signum_update", inputs, attrs).InvokeMulti();
}
// operator sin
inline NDArray sin(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sin", inputs, attrs).Invoke();
}
// operator sinh
inline NDArray sinh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sinh", inputs, attrs).Invoke();
}
// operator size_array
inline NDArray size_array(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("size_array", inputs, attrs).Invoke();
}
// operator slice
inline NDArray slice(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("slice", inputs, attrs).Invoke();
}
// operator slice_axis
inline NDArray slice_axis(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("slice_axis", inputs, attrs).Invoke();
}
// operator slice_like
inline NDArray slice_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("slice_like", inputs, attrs).Invoke();
}
// operator slogdet
inline std::vector<NDArray> slogdet(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("slogdet", inputs, attrs).InvokeMulti();
}
// operator smooth_l1
inline NDArray smooth_l1(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("smooth_l1", inputs, attrs).Invoke();
}
// operator softmax
inline NDArray softmax(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("softmax", inputs, attrs).Invoke();
}
// operator softmax_cross_entropy
inline NDArray softmax_cross_entropy(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("softmax_cross_entropy", inputs, attrs).Invoke();
}
// operator softmin
inline NDArray softmin(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("softmin", inputs, attrs).Invoke();
}
// operator softsign
inline NDArray softsign(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("softsign", inputs, attrs).Invoke();
}
// operator sort
inline NDArray sort(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sort", inputs, attrs).Invoke();
}
// operator space_to_depth
inline NDArray space_to_depth(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("space_to_depth", inputs, attrs).Invoke();
}
// operator sparse_adagrad_update
inline std::vector<NDArray> sparse_adagrad_update(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sparse_adagrad_update", inputs, attrs).InvokeMulti();
}
// operator sparse_retain
inline NDArray sparse_retain(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sparse_retain", inputs, attrs).Invoke();
}
// operator split
inline std::vector<NDArray> split(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("split", inputs, attrs).InvokeMulti();
}
// operator split_v2
inline std::vector<NDArray> split_v2(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("split_v2", inputs, attrs).InvokeMulti();
}
// operator sqrt
inline NDArray sqrt(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sqrt", inputs, attrs).Invoke();
}
// operator square
inline NDArray square(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("square", inputs, attrs).Invoke();
}
// operator square_sum
inline NDArray square_sum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("square_sum", inputs, attrs).Invoke();
}
// operator squeeze
inline NDArray squeeze(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("squeeze", inputs, attrs).Invoke();
}
// operator ssd_scan — inputs: data, dt, A, B, C, D
inline NDArray ssd_scan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("ssd_scan", inputs, attrs).Invoke();
}
// operator stack
inline NDArray stack(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("stack", inputs, attrs).Invoke();
}
// operator stop_gradient
inline NDArray stop_gradient(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("stop_gradient", inputs, attrs).Invoke();
}
// operator sum
inline NDArray sum(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("sum", inputs, attrs).Invoke();
}
// operator swapaxes
inline NDArray swapaxes(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("swapaxes", inputs, attrs).Invoke();
}
// operator take
inline NDArray take(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("take", inputs, attrs).Invoke();
}
// operator tan
inline NDArray tan(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("tan", inputs, attrs).Invoke();
}
// operator tanh
inline NDArray tanh(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("tanh", inputs, attrs).Invoke();
}
// operator tile
inline NDArray tile(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("tile", inputs, attrs).Invoke();
}
// operator topk
inline std::vector<NDArray> topk(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("topk", inputs, attrs).InvokeMulti();
}
// operator transpose
inline NDArray transpose(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("transpose", inputs, attrs).Invoke();
}
// operator trunc
inline NDArray trunc(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("trunc", inputs, attrs).Invoke();
}
// operator uniform
inline NDArray uniform(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("uniform", inputs, attrs).Invoke();
}
// operator unravel_index
inline NDArray unravel_index(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("unravel_index", inputs, attrs).Invoke();
}
// operator where
inline NDArray where(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("where", inputs, attrs).Invoke();
}
// operator zeros_like
inline NDArray zeros_like(const std::vector<NDArray>& inputs,
    const OpAttrs& attrs = {}) {
  return detail::Build("zeros_like", inputs, attrs).Invoke();
}
}  // namespace cpp
}  // namespace mxnet_tpu

#endif  // MXNET_TPU_CPP_OP_HPP_
