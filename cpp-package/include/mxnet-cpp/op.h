/*
 * op.h — GENERATED per-operator Symbol factories (do not edit).
 *
 * Reference: cpp-package/include/mxnet-cpp/op.h (generated by
 * OpWrapperGenerator.py there too). Regenerate with
 *   python cpp-package/OpWrapperGenerator.py
 */
#ifndef MXNET_TPU_CPP_OP_H_
#define MXNET_TPU_CPP_OP_H_

#include <string>
#include <vector>

#include "MxNetCpp.h"

namespace mxnet {
namespace cpp {

namespace detail {
inline std::string ToParam(const std::string &v) { return v; }
inline std::string ToParam(const char *v) { return v; }
inline std::string ToParam(bool v) { return v ? "true" : "false"; }
inline std::string ToParam(int v) { return std::to_string(v); }
inline std::string ToParam(mx_float v) { return std::to_string(v); }
inline std::string ToParam(double v) { return std::to_string(v); }
inline std::string ToParam(const Shape &v) {
  std::string s = "(";
  for (size_t i = 0; i < v.size(); ++i) {
    if (i) s += ",";
    s += std::to_string(v[i]);
  }
  return s + ")";
}
}  // namespace detail

inline Symbol Activation(const std::string &symbol_name,
    const Symbol &data,
    const std::string &act_type = "relu") {
  Operator op("Activation");
  op.SetInput("data", data);
  if (!act_type.empty()) op.SetParam("act_type", detail::ToParam(act_type));
  return op.CreateSymbol(symbol_name);
}

inline Symbol BatchNorm(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const Symbol &beta,
    const Symbol &moving_mean,
    const Symbol &moving_var,
    mx_float eps = 0.001f,
    mx_float momentum = 0.9f,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  Operator op("BatchNorm");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetInput("moving_mean", moving_mean);
  op.SetInput("moving_var", moving_var);
  op.SetParam("eps", detail::ToParam(eps));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("fix_gamma", detail::ToParam(fix_gamma));
  op.SetParam("use_global_stats", detail::ToParam(use_global_stats));
  op.SetParam("output_mean_var", detail::ToParam(output_mean_var));
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  return op.CreateSymbol(symbol_name);
}

inline Symbol BatchNorm_v1(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const Symbol &beta,
    const Symbol &moving_mean,
    const Symbol &moving_var,
    mx_float eps = 0.001f,
    mx_float momentum = 0.9f,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  Operator op("BatchNorm_v1");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetInput("moving_mean", moving_mean);
  op.SetInput("moving_var", moving_var);
  op.SetParam("eps", detail::ToParam(eps));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("fix_gamma", detail::ToParam(fix_gamma));
  op.SetParam("use_global_stats", detail::ToParam(use_global_stats));
  op.SetParam("output_mean_var", detail::ToParam(output_mean_var));
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  return op.CreateSymbol(symbol_name);
}

inline Symbol BilinearSampler(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &grid) {
  Operator op("BilinearSampler");
  op.SetInput("data", data);
  op.SetInput("grid", grid);
  return op.CreateSymbol(symbol_name);
}

inline Symbol BlockGrad(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("BlockGrad");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol Cast(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("Cast");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol Concat(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int dim = 1) {
  Operator op("Concat");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  op.SetParam("dim", detail::ToParam(dim));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Convolution(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    const Shape &kernel = Shape{},
    const Shape &stride = Shape{},
    const Shape &dilate = Shape{},
    const Shape &pad = Shape{},
    int num_filter = 0,
    int num_group = 1,
    bool no_bias = false,
    int workspace = 1024,
    const std::string &cudnn_tune = "",
    bool cudnn_off = false,
    const std::string &layout = "") {
  Operator op("Convolution");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  if (!kernel.empty()) op.SetParam("kernel", detail::ToParam(kernel));
  if (!stride.empty()) op.SetParam("stride", detail::ToParam(stride));
  if (!dilate.empty()) op.SetParam("dilate", detail::ToParam(dilate));
  if (!pad.empty()) op.SetParam("pad", detail::ToParam(pad));
  op.SetParam("num_filter", detail::ToParam(num_filter));
  op.SetParam("num_group", detail::ToParam(num_group));
  op.SetParam("no_bias", detail::ToParam(no_bias));
  op.SetParam("workspace", detail::ToParam(workspace));
  if (!cudnn_tune.empty()) op.SetParam("cudnn_tune", detail::ToParam(cudnn_tune));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  if (!layout.empty()) op.SetParam("layout", detail::ToParam(layout));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Convolution_v1(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    const Shape &kernel = Shape(),
    const Shape &stride = Shape(),
    const Shape &dilate = Shape(),
    const Shape &pad = Shape(),
    int num_filter = 0,
    int num_group = 1,
    int workspace = 1024,
    bool no_bias = false,
    const std::string &cudnn_tune = "",
    bool cudnn_off = false,
    const std::string &layout = "") {
  Operator op("Convolution_v1");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  if (!kernel.empty()) op.SetParam("kernel", detail::ToParam(kernel));
  if (!stride.empty()) op.SetParam("stride", detail::ToParam(stride));
  if (!dilate.empty()) op.SetParam("dilate", detail::ToParam(dilate));
  if (!pad.empty()) op.SetParam("pad", detail::ToParam(pad));
  op.SetParam("num_filter", detail::ToParam(num_filter));
  op.SetParam("num_group", detail::ToParam(num_group));
  op.SetParam("workspace", detail::ToParam(workspace));
  op.SetParam("no_bias", detail::ToParam(no_bias));
  if (!cudnn_tune.empty()) op.SetParam("cudnn_tune", detail::ToParam(cudnn_tune));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  if (!layout.empty()) op.SetParam("layout", detail::ToParam(layout));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Correlation(const std::string &symbol_name,
    const Symbol &data1,
    const Symbol &data2,
    int kernel_size = 1,
    int max_displacement = 1,
    int stride1 = 1,
    int stride2 = 1,
    int pad_size = 0,
    bool is_multiply = true) {
  Operator op("Correlation");
  op.SetInput("data1", data1);
  op.SetInput("data2", data2);
  op.SetParam("kernel_size", detail::ToParam(kernel_size));
  op.SetParam("max_displacement", detail::ToParam(max_displacement));
  op.SetParam("stride1", detail::ToParam(stride1));
  op.SetParam("stride2", detail::ToParam(stride2));
  op.SetParam("pad_size", detail::ToParam(pad_size));
  op.SetParam("is_multiply", detail::ToParam(is_multiply));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Crop(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    const Shape &offset = Shape{0, 0},
    const Shape &h_w = Shape{0, 0},
    bool center_crop = false) {
  Operator op("Crop");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  if (!offset.empty()) op.SetParam("offset", detail::ToParam(offset));
  if (!h_w.empty()) op.SetParam("h_w", detail::ToParam(h_w));
  op.SetParam("center_crop", detail::ToParam(center_crop));
  return op.CreateSymbol(symbol_name);
}

inline Symbol CuDNNBatchNorm(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const Symbol &beta,
    const Symbol &moving_mean,
    const Symbol &moving_var,
    mx_float eps = 0.001f,
    mx_float momentum = 0.9f,
    bool fix_gamma = true,
    bool use_global_stats = false,
    bool output_mean_var = false,
    int axis = 1,
    bool cudnn_off = false) {
  Operator op("CuDNNBatchNorm");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetInput("moving_mean", moving_mean);
  op.SetInput("moving_var", moving_var);
  op.SetParam("eps", detail::ToParam(eps));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("fix_gamma", detail::ToParam(fix_gamma));
  op.SetParam("use_global_stats", detail::ToParam(use_global_stats));
  op.SetParam("output_mean_var", detail::ToParam(output_mean_var));
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Custom(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  Operator op("Custom");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Deconvolution(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    const Shape &kernel = Shape{},
    const Shape &stride = Shape{},
    const Shape &dilate = Shape{},
    const Shape &pad = Shape{},
    const Shape &adj = Shape{},
    const Shape &target_shape = Shape{},
    int num_filter = 0,
    int num_group = 1,
    bool no_bias = true,
    int workspace = 512) {
  Operator op("Deconvolution");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  if (!kernel.empty()) op.SetParam("kernel", detail::ToParam(kernel));
  if (!stride.empty()) op.SetParam("stride", detail::ToParam(stride));
  if (!dilate.empty()) op.SetParam("dilate", detail::ToParam(dilate));
  if (!pad.empty()) op.SetParam("pad", detail::ToParam(pad));
  if (!adj.empty()) op.SetParam("adj", detail::ToParam(adj));
  if (!target_shape.empty()) op.SetParam("target_shape", detail::ToParam(target_shape));
  op.SetParam("num_filter", detail::ToParam(num_filter));
  op.SetParam("num_group", detail::ToParam(num_group));
  op.SetParam("no_bias", detail::ToParam(no_bias));
  op.SetParam("workspace", detail::ToParam(workspace));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Dropout(const std::string &symbol_name,
    const Symbol &data,
    mx_float p = 0.5f,
    const std::string &mode = "training") {
  Operator op("Dropout");
  op.SetInput("data", data);
  op.SetParam("p", detail::ToParam(p));
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  return op.CreateSymbol(symbol_name);
}

inline Symbol ElementWiseSum(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  Operator op("ElementWiseSum");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Embedding(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    int input_dim = 0,
    int output_dim = 0,
    const std::string &dtype = "float32") {
  Operator op("Embedding");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetParam("input_dim", detail::ToParam(input_dim));
  op.SetParam("output_dim", detail::ToParam(output_dim));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Flatten(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("Flatten");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol FullyConnected(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    int num_hidden = 0,
    bool no_bias = false,
    bool flatten = true) {
  Operator op("FullyConnected");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  op.SetParam("num_hidden", detail::ToParam(num_hidden));
  op.SetParam("no_bias", detail::ToParam(no_bias));
  op.SetParam("flatten", detail::ToParam(flatten));
  return op.CreateSymbol(symbol_name);
}

inline Symbol GatedDeltaRule(const std::string &symbol_name,
    const Symbol &query,
    const Symbol &key,
    const Symbol &value,
    const Symbol &g,
    const Symbol &beta,
    const Symbol &stats,
    int num_heads = 1) {
  Operator op("GatedDeltaRule");
  op.SetInput("query", query);
  op.SetInput("key", key);
  op.SetInput("value", value);
  op.SetInput("g", g);
  op.SetInput("beta", beta);
  op.SetInput("stats", stats);
  op.SetParam("num_heads", detail::ToParam(num_heads));
  return op.CreateSymbol(symbol_name);
}

inline Symbol GatedMLP(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &w1_weight,
    const Symbol &w3_weight,
    const Symbol &w2_weight,
    int hidden = 0) {
  Operator op("GatedMLP");
  op.SetInput("data", data);
  op.SetInput("w1_weight", w1_weight);
  op.SetInput("w3_weight", w3_weight);
  op.SetInput("w2_weight", w2_weight);
  op.SetParam("hidden", detail::ToParam(hidden));
  return op.CreateSymbol(symbol_name);
}

inline Symbol GatedShortConv(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    int kernel = 3) {
  Operator op("GatedShortConv");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetParam("kernel", detail::ToParam(kernel));
  return op.CreateSymbol(symbol_name);
}

inline Symbol GridGenerator(const std::string &symbol_name,
    const Symbol &data,
    const std::string &transform_type = "affine",
    const Shape &target_shape = Shape{0, 0}) {
  Operator op("GridGenerator");
  op.SetInput("data", data);
  if (!transform_type.empty()) op.SetParam("transform_type", detail::ToParam(transform_type));
  if (!target_shape.empty()) op.SetParam("target_shape", detail::ToParam(target_shape));
  return op.CreateSymbol(symbol_name);
}

inline Symbol GroupedQueryAttention(const std::string &symbol_name,
    const Symbol &query,
    const Symbol &key,
    const Symbol &value,
    const Symbol &gate,
    int num_heads = 1,
    int num_kv_heads = 1,
    int window = 0,
    bool gated = false,
    int block_length = 0) {
  Operator op("GroupedQueryAttention");
  op.SetInput("query", query);
  op.SetInput("key", key);
  op.SetInput("value", value);
  op.SetInput("gate", gate);
  op.SetParam("num_heads", detail::ToParam(num_heads));
  op.SetParam("num_kv_heads", detail::ToParam(num_kv_heads));
  op.SetParam("window", detail::ToParam(window));
  op.SetParam("gated", detail::ToParam(gated));
  op.SetParam("block_length", detail::ToParam(block_length));
  return op.CreateSymbol(symbol_name);
}

inline Symbol HyperCollapse(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    const Symbol &alpha,
    int n = 4,
    mx_float eps = 1e-06f) {
  Operator op("HyperCollapse");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  op.SetInput("alpha", alpha);
  op.SetParam("n", detail::ToParam(n));
  op.SetParam("eps", detail::ToParam(eps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol HyperPost(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &update,
    const Symbol &coef,
    int n = 4) {
  Operator op("HyperPost");
  op.SetInput("data", data);
  op.SetInput("update", update);
  op.SetInput("coef", coef);
  op.SetParam("n", detail::ToParam(n));
  return op.CreateSymbol(symbol_name);
}

inline Symbol HyperPre(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    const Symbol &bias,
    const Symbol &alpha,
    const Symbol &stats,
    int n = 4,
    mx_float eps = 1e-06f,
    int sinkhorn_iters = 20,
    mx_float sinkhorn_eps = 1e-06f,
    mx_float clamp_min = -30.0f,
    mx_float clamp_max = 30.0f) {
  Operator op("HyperPre");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetInput("bias", bias);
  op.SetInput("alpha", alpha);
  op.SetInput("stats", stats);
  op.SetParam("n", detail::ToParam(n));
  op.SetParam("eps", detail::ToParam(eps));
  op.SetParam("sinkhorn_iters", detail::ToParam(sinkhorn_iters));
  op.SetParam("sinkhorn_eps", detail::ToParam(sinkhorn_eps));
  op.SetParam("clamp_min", detail::ToParam(clamp_min));
  op.SetParam("clamp_max", detail::ToParam(clamp_max));
  return op.CreateSymbol(symbol_name);
}

inline Symbol IdentityAttachKLSparseReg(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &moving_avg,
    mx_float sparseness_target = 0.1f,
    mx_float penalty = 0.001f,
    mx_float momentum = 0.9f) {
  Operator op("IdentityAttachKLSparseReg");
  op.SetInput("data", data);
  op.SetInput("moving_avg", moving_avg);
  op.SetParam("sparseness_target", detail::ToParam(sparseness_target));
  op.SetParam("penalty", detail::ToParam(penalty));
  op.SetParam("momentum", detail::ToParam(momentum));
  return op.CreateSymbol(symbol_name);
}

inline Symbol InstanceNorm(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const Symbol &beta,
    mx_float eps = 0.001f) {
  Operator op("InstanceNorm");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetParam("eps", detail::ToParam(eps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol L2Normalization(const std::string &symbol_name,
    const Symbol &data,
    mx_float eps = 1e-10f,
    const std::string &mode = "instance") {
  Operator op("L2Normalization");
  op.SetInput("data", data);
  op.SetParam("eps", detail::ToParam(eps));
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LRN(const std::string &symbol_name,
    const Symbol &data,
    mx_float alpha = 0.0001f,
    mx_float beta = 0.75f,
    mx_float knorm = 2.0f,
    int nsize = 5) {
  Operator op("LRN");
  op.SetInput("data", data);
  op.SetParam("alpha", detail::ToParam(alpha));
  op.SetParam("beta", detail::ToParam(beta));
  op.SetParam("knorm", detail::ToParam(knorm));
  op.SetParam("nsize", detail::ToParam(nsize));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LatentAttention(const std::string &symbol_name,
    const Symbol &q_nope,
    const Symbol &q_rope,
    const Symbol &k_nope,
    const Symbol &k_rope,
    const Symbol &value,
    int num_heads = 1,
    mx_float scale = 0.0f) {
  Operator op("LatentAttention");
  op.SetInput("q_nope", q_nope);
  op.SetInput("q_rope", q_rope);
  op.SetInput("k_nope", k_nope);
  op.SetInput("k_rope", k_rope);
  op.SetInput("value", value);
  op.SetParam("num_heads", detail::ToParam(num_heads));
  op.SetParam("scale", detail::ToParam(scale));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LayerNorm(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const Symbol &beta,
    int axis = -1,
    mx_float eps = 1e-05f) {
  Operator op("LayerNorm");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetInput("beta", beta);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("eps", detail::ToParam(eps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LeakyReLU(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    const std::string &act_type = "leaky",
    mx_float slope = 0.25f,
    mx_float lower_bound = 0.125f,
    mx_float upper_bound = 0.334f) {
  Operator op("LeakyReLU");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  if (!act_type.empty()) op.SetParam("act_type", detail::ToParam(act_type));
  op.SetParam("slope", detail::ToParam(slope));
  op.SetParam("lower_bound", detail::ToParam(lower_bound));
  op.SetParam("upper_bound", detail::ToParam(upper_bound));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LinearRegressionOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float grad_scale = 1.0f) {
  Operator op("LinearRegressionOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  return op.CreateSymbol(symbol_name);
}

inline Symbol LogisticRegressionOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float grad_scale = 1.0f) {
  Operator op("LogisticRegressionOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  return op.CreateSymbol(symbol_name);
}

inline Symbol MAERegressionOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float grad_scale = 1.0f) {
  Operator op("MAERegressionOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  return op.CreateSymbol(symbol_name);
}

inline Symbol MakeLoss(const std::string &symbol_name,
    const Symbol &data,
    mx_float grad_scale = 1.0f,
    const std::string &normalization = "null",
    mx_float valid_thresh = 0.0f) {
  Operator op("MakeLoss");
  op.SetInput("data", data);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  if (!normalization.empty()) op.SetParam("normalization", detail::ToParam(normalization));
  op.SetParam("valid_thresh", detail::ToParam(valid_thresh));
  return op.CreateSymbol(symbol_name);
}

inline Symbol MoE(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &router_weight,
    const Symbol &experts_w1_weight,
    const Symbol &experts_w3_weight,
    const Symbol &experts_w2_weight,
    const Symbol &shared_w1_weight,
    const Symbol &shared_w3_weight,
    const Symbol &shared_w2_weight,
    const Symbol &stats,
    const Symbol &select_bias,
    int num_experts = 0,
    int num_experts_per_tok = 1,
    int experts_held = 0,
    int expert_offset = 0,
    bool norm_topk_prob = true,
    mx_float routed_scaling = 1.0f,
    int hidden = 0,
    int shared_hidden = 0,
    const std::string &scoring = "softmax",
    mx_float norm_eps = 1e-20f) {
  Operator op("MoE");
  op.SetInput("data", data);
  op.SetInput("router_weight", router_weight);
  op.SetInput("experts_w1_weight", experts_w1_weight);
  op.SetInput("experts_w3_weight", experts_w3_weight);
  op.SetInput("experts_w2_weight", experts_w2_weight);
  op.SetInput("shared_w1_weight", shared_w1_weight);
  op.SetInput("shared_w3_weight", shared_w3_weight);
  op.SetInput("shared_w2_weight", shared_w2_weight);
  op.SetInput("stats", stats);
  op.SetInput("select_bias", select_bias);
  op.SetParam("num_experts", detail::ToParam(num_experts));
  op.SetParam("num_experts_per_tok", detail::ToParam(num_experts_per_tok));
  op.SetParam("experts_held", detail::ToParam(experts_held));
  op.SetParam("expert_offset", detail::ToParam(expert_offset));
  op.SetParam("norm_topk_prob", detail::ToParam(norm_topk_prob));
  op.SetParam("routed_scaling", detail::ToParam(routed_scaling));
  op.SetParam("hidden", detail::ToParam(hidden));
  op.SetParam("shared_hidden", detail::ToParam(shared_hidden));
  if (!scoring.empty()) op.SetParam("scoring", detail::ToParam(scoring));
  op.SetParam("norm_eps", detail::ToParam(norm_eps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Pad(const std::string &symbol_name,
    const Symbol &data,
    const std::string &mode = "constant",
    const Shape &pad_width = Shape{},
    mx_float constant_value = 0.0f) {
  Operator op("Pad");
  op.SetInput("data", data);
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  if (!pad_width.empty()) op.SetParam("pad_width", detail::ToParam(pad_width));
  op.SetParam("constant_value", detail::ToParam(constant_value));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Pooling(const std::string &symbol_name,
    const Symbol &data,
    const Shape &kernel = Shape{},
    const std::string &pool_type = "max",
    const Shape &stride = Shape{},
    const Shape &pad = Shape{},
    bool global_pool = false,
    const std::string &pooling_convention = "valid",
    bool cudnn_off = false) {
  Operator op("Pooling");
  op.SetInput("data", data);
  if (!kernel.empty()) op.SetParam("kernel", detail::ToParam(kernel));
  if (!pool_type.empty()) op.SetParam("pool_type", detail::ToParam(pool_type));
  if (!stride.empty()) op.SetParam("stride", detail::ToParam(stride));
  if (!pad.empty()) op.SetParam("pad", detail::ToParam(pad));
  op.SetParam("global_pool", detail::ToParam(global_pool));
  if (!pooling_convention.empty()) op.SetParam("pooling_convention", detail::ToParam(pooling_convention));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Pooling_v1(const std::string &symbol_name,
    const Symbol &data,
    const Shape &kernel = Shape{},
    const std::string &pool_type = "max",
    const Shape &stride = Shape{},
    const Shape &pad = Shape{},
    bool global_pool = false,
    const std::string &pooling_convention = "valid",
    bool cudnn_off = false) {
  Operator op("Pooling_v1");
  op.SetInput("data", data);
  if (!kernel.empty()) op.SetParam("kernel", detail::ToParam(kernel));
  if (!pool_type.empty()) op.SetParam("pool_type", detail::ToParam(pool_type));
  if (!stride.empty()) op.SetParam("stride", detail::ToParam(stride));
  if (!pad.empty()) op.SetParam("pad", detail::ToParam(pad));
  op.SetParam("global_pool", detail::ToParam(global_pool));
  if (!pooling_convention.empty()) op.SetParam("pooling_convention", detail::ToParam(pooling_convention));
  op.SetParam("cudnn_off", detail::ToParam(cudnn_off));
  return op.CreateSymbol(symbol_name);
}

inline Symbol RMSNorm(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &gamma,
    mx_float eps = 1e-06f) {
  Operator op("RMSNorm");
  op.SetInput("data", data);
  op.SetInput("gamma", gamma);
  op.SetParam("eps", detail::ToParam(eps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol RNN(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &parameters,
    const Symbol &state,
    const Symbol &state_cell,
    int state_size = 0,
    int num_layers = 1,
    bool bidirectional = false,
    const std::string &mode = "lstm",
    mx_float p = 0.0f,
    bool state_outputs = false,
    const std::string &lstm_state_clip_min = "",
    const std::string &lstm_state_clip_max = "") {
  Operator op("RNN");
  op.SetInput("data", data);
  op.SetInput("parameters", parameters);
  op.SetInput("state", state);
  op.SetInput("state_cell", state_cell);
  op.SetParam("state_size", detail::ToParam(state_size));
  op.SetParam("num_layers", detail::ToParam(num_layers));
  op.SetParam("bidirectional", detail::ToParam(bidirectional));
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  op.SetParam("p", detail::ToParam(p));
  op.SetParam("state_outputs", detail::ToParam(state_outputs));
  if (!lstm_state_clip_min.empty()) op.SetParam("lstm_state_clip_min", detail::ToParam(lstm_state_clip_min));
  if (!lstm_state_clip_max.empty()) op.SetParam("lstm_state_clip_max", detail::ToParam(lstm_state_clip_max));
  return op.CreateSymbol(symbol_name);
}

inline Symbol ROIPooling(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &rois,
    const Shape &pooled_size = Shape{0, 0},
    mx_float spatial_scale = 1.0f) {
  Operator op("ROIPooling");
  op.SetInput("data", data);
  op.SetInput("rois", rois);
  if (!pooled_size.empty()) op.SetParam("pooled_size", detail::ToParam(pooled_size));
  op.SetParam("spatial_scale", detail::ToParam(spatial_scale));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Reshape(const std::string &symbol_name,
    const Symbol &data,
    const Shape &shape = Shape{},
    bool reverse = false,
    const Shape &target_shape = Shape{},
    bool keep_highest = false) {
  Operator op("Reshape");
  op.SetInput("data", data);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  op.SetParam("reverse", detail::ToParam(reverse));
  if (!target_shape.empty()) op.SetParam("target_shape", detail::ToParam(target_shape));
  op.SetParam("keep_highest", detail::ToParam(keep_highest));
  return op.CreateSymbol(symbol_name);
}

inline Symbol RotaryEmbedding(const std::string &symbol_name,
    const Symbol &data,
    int num_heads = 1,
    mx_float base = 10000.0f,
    int rotary_dim = 0,
    const std::string &scaling = "default",
    mx_float factor = 1.0f,
    int original_max_position = 0,
    mx_float beta_fast = 32.0f,
    mx_float beta_slow = 1.0f,
    mx_float attention_factor = 0.0f,
    bool interleaved = false,
    int period = 0) {
  Operator op("RotaryEmbedding");
  op.SetInput("data", data);
  op.SetParam("num_heads", detail::ToParam(num_heads));
  op.SetParam("base", detail::ToParam(base));
  op.SetParam("rotary_dim", detail::ToParam(rotary_dim));
  if (!scaling.empty()) op.SetParam("scaling", detail::ToParam(scaling));
  op.SetParam("factor", detail::ToParam(factor));
  op.SetParam("original_max_position", detail::ToParam(original_max_position));
  op.SetParam("beta_fast", detail::ToParam(beta_fast));
  op.SetParam("beta_slow", detail::ToParam(beta_slow));
  op.SetParam("attention_factor", detail::ToParam(attention_factor));
  op.SetParam("interleaved", detail::ToParam(interleaved));
  op.SetParam("period", detail::ToParam(period));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SVMOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float margin = 1.0f,
    mx_float regularization_coefficient = 1.0f,
    bool use_linear = false) {
  Operator op("SVMOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("margin", detail::ToParam(margin));
  op.SetParam("regularization_coefficient", detail::ToParam(regularization_coefficient));
  op.SetParam("use_linear", detail::ToParam(use_linear));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SequenceLast(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  Operator op("SequenceLast");
  op.SetInput("data", data);
  op.SetInput("sequence_length", sequence_length);
  op.SetParam("use_sequence_length", detail::ToParam(use_sequence_length));
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SequenceMask(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &sequence_length,
    bool use_sequence_length = false,
    mx_float value = 0.0f,
    int axis = 0) {
  Operator op("SequenceMask");
  op.SetInput("data", data);
  op.SetInput("sequence_length", sequence_length);
  op.SetParam("use_sequence_length", detail::ToParam(use_sequence_length));
  op.SetParam("value", detail::ToParam(value));
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SequenceReverse(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &sequence_length,
    bool use_sequence_length = false,
    int axis = 0) {
  Operator op("SequenceReverse");
  op.SetInput("data", data);
  op.SetInput("sequence_length", sequence_length);
  op.SetParam("use_sequence_length", detail::ToParam(use_sequence_length));
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol ShortConv(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &weight,
    int kernel = 4) {
  Operator op("ShortConv");
  op.SetInput("data", data);
  op.SetInput("weight", weight);
  op.SetParam("kernel", detail::ToParam(kernel));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SliceChannel(const std::string &symbol_name,
    const Symbol &data,
    int num_outputs = 1,
    int axis = 1,
    bool squeeze_axis = false) {
  Operator op("SliceChannel");
  op.SetInput("data", data);
  op.SetParam("num_outputs", detail::ToParam(num_outputs));
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("squeeze_axis", detail::ToParam(squeeze_axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol Softmax(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float grad_scale = 1.0f,
    mx_float ignore_label = -1.0f,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string &normalization = "null",
    bool out_grad = false,
    mx_float smooth_alpha = 0.0f) {
  Operator op("Softmax");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  op.SetParam("ignore_label", detail::ToParam(ignore_label));
  op.SetParam("multi_output", detail::ToParam(multi_output));
  op.SetParam("use_ignore", detail::ToParam(use_ignore));
  op.SetParam("preserve_shape", detail::ToParam(preserve_shape));
  if (!normalization.empty()) op.SetParam("normalization", detail::ToParam(normalization));
  op.SetParam("out_grad", detail::ToParam(out_grad));
  op.SetParam("smooth_alpha", detail::ToParam(smooth_alpha));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SoftmaxActivation(const std::string &symbol_name,
    const Symbol &data,
    const std::string &mode = "instance") {
  Operator op("SoftmaxActivation");
  op.SetInput("data", data);
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SoftmaxOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    mx_float grad_scale = 1.0f,
    mx_float ignore_label = -1.0f,
    bool multi_output = false,
    bool use_ignore = false,
    bool preserve_shape = false,
    const std::string &normalization = "null",
    bool out_grad = false,
    mx_float smooth_alpha = 0.0f) {
  Operator op("SoftmaxOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  op.SetParam("ignore_label", detail::ToParam(ignore_label));
  op.SetParam("multi_output", detail::ToParam(multi_output));
  op.SetParam("use_ignore", detail::ToParam(use_ignore));
  op.SetParam("preserve_shape", detail::ToParam(preserve_shape));
  if (!normalization.empty()) op.SetParam("normalization", detail::ToParam(normalization));
  op.SetParam("out_grad", detail::ToParam(out_grad));
  op.SetParam("smooth_alpha", detail::ToParam(smooth_alpha));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SpatialTransformer(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &loc,
    const Shape &target_shape = Shape{0, 0},
    const std::string &transform_type = "affine",
    const std::string &sampler_type = "bilinear") {
  Operator op("SpatialTransformer");
  op.SetInput("data", data);
  op.SetInput("loc", loc);
  if (!target_shape.empty()) op.SetParam("target_shape", detail::ToParam(target_shape));
  if (!transform_type.empty()) op.SetParam("transform_type", detail::ToParam(transform_type));
  if (!sampler_type.empty()) op.SetParam("sampler_type", detail::ToParam(sampler_type));
  return op.CreateSymbol(symbol_name);
}

inline Symbol SwapAxis(const std::string &symbol_name,
    const Symbol &data,
    int dim1 = 0,
    int dim2 = 0) {
  Operator op("SwapAxis");
  op.SetInput("data", data);
  op.SetParam("dim1", detail::ToParam(dim1));
  op.SetParam("dim2", detail::ToParam(dim2));
  return op.CreateSymbol(symbol_name);
}

inline Symbol UpSampling(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int scale = 1,
    const std::string &sample_type = "nearest",
    int num_filter = 0) {
  Operator op("UpSampling");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  op.SetParam("scale", detail::ToParam(scale));
  if (!sample_type.empty()) op.SetParam("sample_type", detail::ToParam(sample_type));
  op.SetParam("num_filter", detail::ToParam(num_filter));
  return op.CreateSymbol(symbol_name);
}

inline Symbol WeightedSoftmaxOutput(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    const Symbol &weight,
    mx_float grad_scale = 1.0f,
    mx_float ignore_label = -1.0f,
    const std::string &normalization = "batch") {
  Operator op("WeightedSoftmaxOutput");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetInput("weight", weight);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  op.SetParam("ignore_label", detail::ToParam(ignore_label));
  if (!normalization.empty()) op.SetParam("normalization", detail::ToParam(normalization));
  return op.CreateSymbol(symbol_name);
}

inline Symbol abs(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("abs");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol adam_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &mean,
    const Symbol &var,
    mx_float lr = 0.001f,
    mx_float beta1 = 0.9f,
    mx_float beta2 = 0.999f,
    mx_float epsilon = 1e-08f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("adam_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mean", mean);
  op.SetInput("var", var);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("beta1", detail::ToParam(beta1));
  op.SetParam("beta2", detail::ToParam(beta2));
  op.SetParam("epsilon", detail::ToParam(epsilon));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol add_n(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  Operator op("add_n");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  return op.CreateSymbol(symbol_name);
}

inline Symbol arccos(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arccos");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol arccosh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arccosh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol arcsin(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arcsin");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol arcsinh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arcsinh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol arctan(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arctan");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol arctanh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("arctanh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol argmax(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false) {
  Operator op("argmax");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  return op.CreateSymbol(symbol_name);
}

inline Symbol argmax_channel(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("argmax_channel");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol argmin(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false) {
  Operator op("argmin");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  return op.CreateSymbol(symbol_name);
}

inline Symbol argsort(const std::string &symbol_name,
    const Symbol &data,
    int axis = -1,
    bool is_ascend = true,
    const std::string &dtype = "float32") {
  Operator op("argsort");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("is_ascend", detail::ToParam(is_ascend));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol batch_dot(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  Operator op("batch_dot");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  op.SetParam("transpose_a", detail::ToParam(transpose_a));
  op.SetParam("transpose_b", detail::ToParam(transpose_b));
  return op.CreateSymbol(symbol_name);
}

inline Symbol batch_take(const std::string &symbol_name,
    const Symbol &a,
    const Symbol &indices) {
  Operator op("batch_take");
  op.SetInput("a", a);
  op.SetInput("indices", indices);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_add(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_add");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_axes(const std::string &symbol_name,
    const Symbol &data,
    const Shape &axis = Shape{},
    const Shape &size = Shape{}) {
  Operator op("broadcast_axes");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  if (!size.empty()) op.SetParam("size", detail::ToParam(size));
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_axis(const std::string &symbol_name,
    const Symbol &data,
    const Shape &axis = Shape{},
    const Shape &size = Shape{}) {
  Operator op("broadcast_axis");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  if (!size.empty()) op.SetParam("size", detail::ToParam(size));
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_div(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_div");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_equal(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_equal");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_greater(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_greater");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_greater_equal(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_greater_equal");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_hypot(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_hypot");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_lesser(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_lesser");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_lesser_equal(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_lesser_equal");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_like(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_like");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_and(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_logical_and");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_or(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_logical_or");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_logical_xor(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_logical_xor");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_maximum(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_maximum");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_minimum(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_minimum");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_minus(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_minus");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_mod(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_mod");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_mul(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_mul");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_not_equal(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_not_equal");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_plus(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_plus");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_power(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_power");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_sub(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("broadcast_sub");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol broadcast_to(const std::string &symbol_name,
    const Symbol &data,
    const Shape &shape = Shape{}) {
  Operator op("broadcast_to");
  op.SetInput("data", data);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  return op.CreateSymbol(symbol_name);
}

inline Symbol cast(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("cast");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol cast_storage(const std::string &symbol_name,
    const Symbol &data,
    const std::string &stype = "default") {
  Operator op("cast_storage");
  op.SetInput("data", data);
  if (!stype.empty()) op.SetParam("stype", detail::ToParam(stype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol cbrt(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("cbrt");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol ceil(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("ceil");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol clip(const std::string &symbol_name,
    const Symbol &data,
    mx_float a_min = 0.0f,
    mx_float a_max = 0.0f) {
  Operator op("clip");
  op.SetInput("data", data);
  op.SetParam("a_min", detail::ToParam(a_min));
  op.SetParam("a_max", detail::ToParam(a_max));
  return op.CreateSymbol(symbol_name);
}

inline Symbol concat(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int dim = 1) {
  Operator op("concat");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  op.SetParam("dim", detail::ToParam(dim));
  return op.CreateSymbol(symbol_name);
}

inline Symbol cos(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("cos");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol cosh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("cosh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol crop(const std::string &symbol_name,
    const Symbol &data,
    const Shape &begin = Shape{},
    const Shape &end = Shape{},
    const std::string &step = "") {
  Operator op("crop");
  op.SetInput("data", data);
  if (!begin.empty()) op.SetParam("begin", detail::ToParam(begin));
  if (!end.empty()) op.SetParam("end", detail::ToParam(end));
  if (!step.empty()) op.SetParam("step", detail::ToParam(step));
  return op.CreateSymbol(symbol_name);
}

inline Symbol ctc_loss(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label,
    const Symbol &data_lengths,
    const Symbol &label_lengths,
    bool use_data_lengths = false,
    bool use_label_lengths = false,
    const std::string &blank_label = "first",
    const std::string &padding_mask = "") {
  Operator op("ctc_loss");
  op.SetInput("data", data);
  op.SetInput("label", label);
  op.SetInput("data_lengths", data_lengths);
  op.SetInput("label_lengths", label_lengths);
  op.SetParam("use_data_lengths", detail::ToParam(use_data_lengths));
  op.SetParam("use_label_lengths", detail::ToParam(use_label_lengths));
  if (!blank_label.empty()) op.SetParam("blank_label", detail::ToParam(blank_label));
  if (!padding_mask.empty()) op.SetParam("padding_mask", detail::ToParam(padding_mask));
  return op.CreateSymbol(symbol_name);
}

inline Symbol degrees(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("degrees");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol depth_to_space(const std::string &symbol_name,
    const Symbol &data,
    int block_size = 1) {
  Operator op("depth_to_space");
  op.SetInput("data", data);
  op.SetParam("block_size", detail::ToParam(block_size));
  return op.CreateSymbol(symbol_name);
}

inline Symbol dot(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs,
    bool transpose_a = false,
    bool transpose_b = false) {
  Operator op("dot");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  op.SetParam("transpose_a", detail::ToParam(transpose_a));
  op.SetParam("transpose_b", detail::ToParam(transpose_b));
  return op.CreateSymbol(symbol_name);
}

inline Symbol elemwise_add(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("elemwise_add");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol elemwise_div(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("elemwise_div");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol elemwise_mul(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("elemwise_mul");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol elemwise_sub(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("elemwise_sub");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol erf(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("erf");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol exp(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("exp");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol expand_dims(const std::string &symbol_name,
    const Symbol &data,
    int axis = 0) {
  Operator op("expand_dims");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol expm1(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("expm1");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol fix(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("fix");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol flatten(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("flatten");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol flip(const std::string &symbol_name,
    const Symbol &data,
    const Shape &axis = Shape{}) {
  Operator op("flip");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol floor(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("floor");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol ftrl_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &z,
    const Symbol &n,
    mx_float lr = 0.1f,
    mx_float lamda1 = 0.01f,
    mx_float beta = 1.0f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("ftrl_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("z", z);
  op.SetInput("n", n);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("lamda1", detail::ToParam(lamda1));
  op.SetParam("beta", detail::ToParam(beta));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol gamma(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("gamma");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol gammaln(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("gammaln");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol gather_nd(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &indices) {
  Operator op("gather_nd");
  op.SetInput("data", data);
  op.SetInput("indices", indices);
  return op.CreateSymbol(symbol_name);
}

inline Symbol identity(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("identity");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol khatri_rao(const std::string &symbol_name,
    const std::vector<Symbol> &data) {
  Operator op("khatri_rao");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_gelqf(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("linalg_gelqf");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_gemm(const std::string &symbol_name,
    const Symbol &A,
    const Symbol &B,
    const Symbol &C,
    bool transpose_a = false,
    bool transpose_b = false,
    mx_float alpha = 1.0f,
    mx_float beta = 1.0f,
    int axis = -2) {
  Operator op("linalg_gemm");
  op.SetInput("A", A);
  op.SetInput("B", B);
  op.SetInput("C", C);
  op.SetParam("transpose_a", detail::ToParam(transpose_a));
  op.SetParam("transpose_b", detail::ToParam(transpose_b));
  op.SetParam("alpha", detail::ToParam(alpha));
  op.SetParam("beta", detail::ToParam(beta));
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_gemm2(const std::string &symbol_name,
    const Symbol &A,
    const Symbol &B,
    bool transpose_a = false,
    bool transpose_b = false,
    mx_float alpha = 1.0f) {
  Operator op("linalg_gemm2");
  op.SetInput("A", A);
  op.SetInput("B", B);
  op.SetParam("transpose_a", detail::ToParam(transpose_a));
  op.SetParam("transpose_b", detail::ToParam(transpose_b));
  op.SetParam("alpha", detail::ToParam(alpha));
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_potrf(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("linalg_potrf");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_potri(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("linalg_potri");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_sumlogdiag(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("linalg_sumlogdiag");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_syrk(const std::string &symbol_name,
    const Symbol &data,
    bool transpose = false,
    mx_float alpha = 1.0f) {
  Operator op("linalg_syrk");
  op.SetInput("data", data);
  op.SetParam("transpose", detail::ToParam(transpose));
  op.SetParam("alpha", detail::ToParam(alpha));
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_trmm(const std::string &symbol_name,
    const Symbol &A,
    const Symbol &B,
    bool transpose = false,
    bool rightside = false,
    mx_float alpha = 1.0f,
    bool lower = true) {
  Operator op("linalg_trmm");
  op.SetInput("A", A);
  op.SetInput("B", B);
  op.SetParam("transpose", detail::ToParam(transpose));
  op.SetParam("rightside", detail::ToParam(rightside));
  op.SetParam("alpha", detail::ToParam(alpha));
  op.SetParam("lower", detail::ToParam(lower));
  return op.CreateSymbol(symbol_name);
}

inline Symbol linalg_trsm(const std::string &symbol_name,
    const Symbol &A,
    const Symbol &B,
    bool transpose = false,
    bool rightside = false,
    mx_float alpha = 1.0f,
    bool lower = true) {
  Operator op("linalg_trsm");
  op.SetInput("A", A);
  op.SetInput("B", B);
  op.SetParam("transpose", detail::ToParam(transpose));
  op.SetParam("rightside", detail::ToParam(rightside));
  op.SetParam("alpha", detail::ToParam(alpha));
  op.SetParam("lower", detail::ToParam(lower));
  return op.CreateSymbol(symbol_name);
}

inline Symbol log(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("log");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol log10(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("log10");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol log1p(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("log1p");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol log2(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("log2");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol log_softmax(const std::string &symbol_name,
    const Symbol &data,
    int axis = -1,
    const std::string &temperature = "") {
  Operator op("log_softmax");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  if (!temperature.empty()) op.SetParam("temperature", detail::ToParam(temperature));
  return op.CreateSymbol(symbol_name);
}

inline Symbol logical_not(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("logical_not");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol make_loss(const std::string &symbol_name,
    const Symbol &data,
    mx_float grad_scale = 1.0f,
    const std::string &normalization = "null",
    mx_float valid_thresh = 0.0f) {
  Operator op("make_loss");
  op.SetInput("data", data);
  op.SetParam("grad_scale", detail::ToParam(grad_scale));
  if (!normalization.empty()) op.SetParam("normalization", detail::ToParam(normalization));
  op.SetParam("valid_thresh", detail::ToParam(valid_thresh));
  return op.CreateSymbol(symbol_name);
}

inline Symbol max(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("max");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol max_axis(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("max_axis");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol mean(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("mean");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol min(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("min");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol min_axis(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("min_axis");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol mp_sgd_mom_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &mom,
    const Symbol &weight32,
    mx_float lr = 0.01f,
    mx_float momentum = 0.0f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("mp_sgd_mom_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetInput("weight32", weight32);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol mp_sgd_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &weight32,
    mx_float lr = 0.01f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("mp_sgd_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("weight32", weight32);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol nag_mom_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &mom,
    mx_float lr = 0.01f,
    mx_float momentum = 0.0f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("nag_mom_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol nanprod(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("nanprod");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol nansum(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("nansum");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol negative(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("negative");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol norm(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    int ord = 2) {
  Operator op("norm");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("ord", detail::ToParam(ord));
  return op.CreateSymbol(symbol_name);
}

inline Symbol normal(const std::string &symbol_name,
    mx_float loc = 0.0f,
    mx_float scale = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("normal");
  op.SetParam("loc", detail::ToParam(loc));
  op.SetParam("scale", detail::ToParam(scale));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol one_hot(const std::string &symbol_name,
    const Symbol &data,
    int depth = 0,
    mx_float on_value = 1.0f,
    mx_float off_value = 0.0f,
    const std::string &dtype = "float32") {
  Operator op("one_hot");
  op.SetInput("data", data);
  op.SetParam("depth", detail::ToParam(depth));
  op.SetParam("on_value", detail::ToParam(on_value));
  op.SetParam("off_value", detail::ToParam(off_value));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol ones_like(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("ones_like");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol pad(const std::string &symbol_name,
    const Symbol &data,
    const std::string &mode = "constant",
    const Shape &pad_width = Shape{},
    mx_float constant_value = 0.0f) {
  Operator op("pad");
  op.SetInput("data", data);
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  if (!pad_width.empty()) op.SetParam("pad_width", detail::ToParam(pad_width));
  op.SetParam("constant_value", detail::ToParam(constant_value));
  return op.CreateSymbol(symbol_name);
}

inline Symbol pick(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &index,
    int axis = -1,
    bool keepdims = false) {
  Operator op("pick");
  op.SetInput("data", data);
  op.SetInput("index", index);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  return op.CreateSymbol(symbol_name);
}

inline Symbol pow(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("pow");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol prod(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("prod");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol radians(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("radians");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_exponential(const std::string &symbol_name,
    mx_float lam = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_exponential");
  op.SetParam("lam", detail::ToParam(lam));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_gamma(const std::string &symbol_name,
    mx_float alpha = 1.0f,
    mx_float beta = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_gamma");
  op.SetParam("alpha", detail::ToParam(alpha));
  op.SetParam("beta", detail::ToParam(beta));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_generalized_negative_binomial(const std::string &symbol_name,
    mx_float mu = 1.0f,
    mx_float alpha = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_generalized_negative_binomial");
  op.SetParam("mu", detail::ToParam(mu));
  op.SetParam("alpha", detail::ToParam(alpha));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_negative_binomial(const std::string &symbol_name,
    int k = 1,
    mx_float p = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_negative_binomial");
  op.SetParam("k", detail::ToParam(k));
  op.SetParam("p", detail::ToParam(p));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_normal(const std::string &symbol_name,
    mx_float loc = 0.0f,
    mx_float scale = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_normal");
  op.SetParam("loc", detail::ToParam(loc));
  op.SetParam("scale", detail::ToParam(scale));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_poisson(const std::string &symbol_name,
    mx_float lam = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_poisson");
  op.SetParam("lam", detail::ToParam(lam));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol random_uniform(const std::string &symbol_name,
    mx_float low = 0.0f,
    mx_float high = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("random_uniform");
  op.SetParam("low", detail::ToParam(low));
  op.SetParam("high", detail::ToParam(high));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol rcbrt(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("rcbrt");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol reciprocal(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("reciprocal");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol relu(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("relu");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol repeat(const std::string &symbol_name,
    const Symbol &data,
    int repeats = 1,
    const std::string &axis = "") {
  Operator op("repeat");
  op.SetInput("data", data);
  op.SetParam("repeats", detail::ToParam(repeats));
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol reshape(const std::string &symbol_name,
    const Symbol &data,
    const Shape &shape = Shape{},
    bool reverse = false,
    const Shape &target_shape = Shape{},
    bool keep_highest = false) {
  Operator op("reshape");
  op.SetInput("data", data);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  op.SetParam("reverse", detail::ToParam(reverse));
  if (!target_shape.empty()) op.SetParam("target_shape", detail::ToParam(target_shape));
  op.SetParam("keep_highest", detail::ToParam(keep_highest));
  return op.CreateSymbol(symbol_name);
}

inline Symbol reshape_like(const std::string &symbol_name,
    const Symbol &lhs,
    const Symbol &rhs) {
  Operator op("reshape_like");
  op.SetInput("lhs", lhs);
  op.SetInput("rhs", rhs);
  return op.CreateSymbol(symbol_name);
}

inline Symbol reverse(const std::string &symbol_name,
    const Symbol &data,
    const Shape &axis = Shape{}) {
  Operator op("reverse");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol rint(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("rint");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol rmsprop_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &n,
    mx_float lr = 0.001f,
    mx_float gamma1 = 0.95f,
    mx_float epsilon = 1e-08f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f,
    mx_float clip_weights = -1.0f) {
  Operator op("rmsprop_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("n", n);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("gamma1", detail::ToParam(gamma1));
  op.SetParam("epsilon", detail::ToParam(epsilon));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  op.SetParam("clip_weights", detail::ToParam(clip_weights));
  return op.CreateSymbol(symbol_name);
}

inline Symbol rmspropalex_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &n,
    const Symbol &g,
    const Symbol &delta,
    mx_float lr = 0.001f,
    mx_float gamma1 = 0.95f,
    mx_float gamma2 = 0.9f,
    mx_float epsilon = 1e-08f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f,
    mx_float clip_weights = -1.0f) {
  Operator op("rmspropalex_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("n", n);
  op.SetInput("g", g);
  op.SetInput("delta", delta);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("gamma1", detail::ToParam(gamma1));
  op.SetParam("gamma2", detail::ToParam(gamma2));
  op.SetParam("epsilon", detail::ToParam(epsilon));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  op.SetParam("clip_weights", detail::ToParam(clip_weights));
  return op.CreateSymbol(symbol_name);
}

inline Symbol round(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("round");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol rsqrt(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("rsqrt");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_exponential(const std::string &symbol_name,
    const Symbol &lam,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("sample_exponential");
  op.SetInput("lam", lam);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_gamma(const std::string &symbol_name,
    const Symbol &alpha,
    const Symbol &beta,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("sample_gamma");
  op.SetInput("alpha", alpha);
  op.SetInput("beta", beta);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_multinomial(const std::string &symbol_name,
    const Symbol &data,
    const Shape &shape = Shape{},
    bool get_prob = false,
    const std::string &dtype = "int32") {
  Operator op("sample_multinomial");
  op.SetInput("data", data);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  op.SetParam("get_prob", detail::ToParam(get_prob));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_normal(const std::string &symbol_name,
    const Symbol &mu,
    const Symbol &sigma,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("sample_normal");
  op.SetInput("mu", mu);
  op.SetInput("sigma", sigma);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_poisson(const std::string &symbol_name,
    const Symbol &lam,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("sample_poisson");
  op.SetInput("lam", lam);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sample_uniform(const std::string &symbol_name,
    const Symbol &low,
    const Symbol &high,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("sample_uniform");
  op.SetInput("low", low);
  op.SetInput("high", high);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol scatter_nd(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &indices,
    const Shape &shape = Shape{}) {
  Operator op("scatter_nd");
  op.SetInput("data", data);
  op.SetInput("indices", indices);
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sgd_mom_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    const Symbol &mom,
    mx_float lr = 0.01f,
    mx_float momentum = 0.0f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("sgd_mom_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetInput("mom", mom);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("momentum", detail::ToParam(momentum));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sgd_update(const std::string &symbol_name,
    const Symbol &weight,
    const Symbol &grad,
    mx_float lr = 0.01f,
    mx_float wd = 0.0f,
    mx_float rescale_grad = 1.0f,
    mx_float clip_gradient = -1.0f) {
  Operator op("sgd_update");
  op.SetInput("weight", weight);
  op.SetInput("grad", grad);
  op.SetParam("lr", detail::ToParam(lr));
  op.SetParam("wd", detail::ToParam(wd));
  op.SetParam("rescale_grad", detail::ToParam(rescale_grad));
  op.SetParam("clip_gradient", detail::ToParam(clip_gradient));
  return op.CreateSymbol(symbol_name);
}

inline Symbol shuffle(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("shuffle");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sigmoid(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("sigmoid");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sign(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("sign");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sin(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("sin");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sinh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("sinh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol slice(const std::string &symbol_name,
    const Symbol &data,
    const Shape &begin = Shape{},
    const Shape &end = Shape{},
    const std::string &step = "") {
  Operator op("slice");
  op.SetInput("data", data);
  if (!begin.empty()) op.SetParam("begin", detail::ToParam(begin));
  if (!end.empty()) op.SetParam("end", detail::ToParam(end));
  if (!step.empty()) op.SetParam("step", detail::ToParam(step));
  return op.CreateSymbol(symbol_name);
}

inline Symbol slice_axis(const std::string &symbol_name,
    const Symbol &data,
    int axis = 0,
    int begin = 0,
    const std::string &end = "") {
  Operator op("slice_axis");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("begin", detail::ToParam(begin));
  if (!end.empty()) op.SetParam("end", detail::ToParam(end));
  return op.CreateSymbol(symbol_name);
}

inline Symbol slice_like(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &shape_like,
    const Shape &axes = Shape{}) {
  Operator op("slice_like");
  op.SetInput("data", data);
  op.SetInput("shape_like", shape_like);
  if (!axes.empty()) op.SetParam("axes", detail::ToParam(axes));
  return op.CreateSymbol(symbol_name);
}

inline Symbol smooth_l1(const std::string &symbol_name,
    const Symbol &data,
    mx_float scalar = 1.0f) {
  Operator op("smooth_l1");
  op.SetInput("data", data);
  op.SetParam("scalar", detail::ToParam(scalar));
  return op.CreateSymbol(symbol_name);
}

inline Symbol softmax(const std::string &symbol_name,
    const Symbol &data,
    int axis = -1,
    const std::string &temperature = "") {
  Operator op("softmax");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  if (!temperature.empty()) op.SetParam("temperature", detail::ToParam(temperature));
  return op.CreateSymbol(symbol_name);
}

inline Symbol softmax_cross_entropy(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &label) {
  Operator op("softmax_cross_entropy");
  op.SetInput("data", data);
  op.SetInput("label", label);
  return op.CreateSymbol(symbol_name);
}

inline Symbol softsign(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("softsign");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sort(const std::string &symbol_name,
    const Symbol &data,
    int axis = -1,
    bool is_ascend = true) {
  Operator op("sort");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("is_ascend", detail::ToParam(is_ascend));
  return op.CreateSymbol(symbol_name);
}

inline Symbol space_to_depth(const std::string &symbol_name,
    const Symbol &data,
    int block_size = 1) {
  Operator op("space_to_depth");
  op.SetInput("data", data);
  op.SetParam("block_size", detail::ToParam(block_size));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sparse_retain(const std::string &symbol_name,
    const Symbol &data,
    const Symbol &indices) {
  Operator op("sparse_retain");
  op.SetInput("data", data);
  op.SetInput("indices", indices);
  return op.CreateSymbol(symbol_name);
}

inline Symbol split(const std::string &symbol_name,
    const Symbol &data,
    int num_outputs = 1,
    int axis = 1,
    bool squeeze_axis = false) {
  Operator op("split");
  op.SetInput("data", data);
  op.SetParam("num_outputs", detail::ToParam(num_outputs));
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("squeeze_axis", detail::ToParam(squeeze_axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sqrt(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("sqrt");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol square(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("square");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol squeeze(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "") {
  Operator op("squeeze");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol stack(const std::string &symbol_name,
    const std::vector<Symbol> &data,
    int axis = 0) {
  Operator op("stack");
  for (const auto &s : data) op.SetInput("", s);
  op.SetParam("num_args", detail::ToParam((int)data.size()));
  op.SetParam("axis", detail::ToParam(axis));
  return op.CreateSymbol(symbol_name);
}

inline Symbol stop_gradient(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("stop_gradient");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol stop_gradient_off(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("stop_gradient_off");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol sum(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("sum");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol sum_axis(const std::string &symbol_name,
    const Symbol &data,
    const std::string &axis = "",
    bool keepdims = false,
    bool exclude = false) {
  Operator op("sum_axis");
  op.SetInput("data", data);
  if (!axis.empty()) op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("keepdims", detail::ToParam(keepdims));
  op.SetParam("exclude", detail::ToParam(exclude));
  return op.CreateSymbol(symbol_name);
}

inline Symbol swapaxes(const std::string &symbol_name,
    const Symbol &data,
    int dim1 = 0,
    int dim2 = 0) {
  Operator op("swapaxes");
  op.SetInput("data", data);
  op.SetParam("dim1", detail::ToParam(dim1));
  op.SetParam("dim2", detail::ToParam(dim2));
  return op.CreateSymbol(symbol_name);
}

inline Symbol take(const std::string &symbol_name,
    const Symbol &a,
    const Symbol &indices,
    int axis = 0,
    const std::string &mode = "clip") {
  Operator op("take");
  op.SetInput("a", a);
  op.SetInput("indices", indices);
  op.SetParam("axis", detail::ToParam(axis));
  if (!mode.empty()) op.SetParam("mode", detail::ToParam(mode));
  return op.CreateSymbol(symbol_name);
}

inline Symbol tan(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("tan");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol tanh(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("tanh");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol tile(const std::string &symbol_name,
    const Symbol &data,
    const Shape &reps = Shape{}) {
  Operator op("tile");
  op.SetInput("data", data);
  if (!reps.empty()) op.SetParam("reps", detail::ToParam(reps));
  return op.CreateSymbol(symbol_name);
}

inline Symbol topk(const std::string &symbol_name,
    const Symbol &data,
    int axis = -1,
    int k = 1,
    const std::string &ret_typ = "indices",
    bool is_ascend = false,
    const std::string &dtype = "float32") {
  Operator op("topk");
  op.SetInput("data", data);
  op.SetParam("axis", detail::ToParam(axis));
  op.SetParam("k", detail::ToParam(k));
  if (!ret_typ.empty()) op.SetParam("ret_typ", detail::ToParam(ret_typ));
  op.SetParam("is_ascend", detail::ToParam(is_ascend));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol transpose(const std::string &symbol_name,
    const Symbol &data,
    const Shape &axes = Shape{}) {
  Operator op("transpose");
  op.SetInput("data", data);
  if (!axes.empty()) op.SetParam("axes", detail::ToParam(axes));
  return op.CreateSymbol(symbol_name);
}

inline Symbol trunc(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("trunc");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}

inline Symbol uniform(const std::string &symbol_name,
    mx_float low = 0.0f,
    mx_float high = 1.0f,
    const Shape &shape = Shape{},
    const std::string &dtype = "float32") {
  Operator op("uniform");
  op.SetParam("low", detail::ToParam(low));
  op.SetParam("high", detail::ToParam(high));
  if (!shape.empty()) op.SetParam("shape", detail::ToParam(shape));
  if (!dtype.empty()) op.SetParam("dtype", detail::ToParam(dtype));
  return op.CreateSymbol(symbol_name);
}

inline Symbol where(const std::string &symbol_name,
    const Symbol &condition,
    const Symbol &x,
    const Symbol &y) {
  Operator op("where");
  op.SetInput("condition", condition);
  op.SetInput("x", x);
  op.SetInput("y", y);
  return op.CreateSymbol(symbol_name);
}

inline Symbol zeros_like(const std::string &symbol_name,
    const Symbol &data) {
  Operator op("zeros_like");
  op.SetInput("data", data);
  return op.CreateSymbol(symbol_name);
}
}  // namespace cpp
}  // namespace mxnet

#endif  /* MXNET_TPU_CPP_OP_H_ */
