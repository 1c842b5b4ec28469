// C predict ABI — the non-Python deployment path.
//
// Reference: include/mxnet/c_predict_api.h + src/c_api/c_predict_api.cc:680
// (MXPredCreate/SetInput/Forward/GetOutput over a bound executor).
//
// TPU-native architecture: the compute path is jax/XLA, which lives in
// CPython — so this shim EMBEDS the interpreter (libpython) and drives
// mxnet_tpu.predict_embed. The C surface is a faithful subset of the
// reference ABI; the program that executes is the same jit-compiled XLA
// computation a Python caller would get (no second engine to maintain,
// no drift between deployment and training numerics).
//
// Build (see src/predict/build.sh):
//   g++ -O2 -std=c++17 -shared -fPIC c_predict_api.cc \
//       $(python3-config --includes) -L$(python3-config --prefix)/lib \
//       -lpython3.12 -o libmxnet_tpu_predict.so
//
// Threading: every entry point takes the GIL (PyGILState_Ensure); the
// embedded interpreter is initialized once, lazily, and configured with
// JAX_PLATFORMS from the environment (CPU by default for portability).

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <mutex>
#include <string>
#include <vector>

typedef uint32_t mx_uint;
typedef float mx_float;
typedef void *PredictorHandle;
typedef void *NDListHandle;

#define MXTPU_API extern "C" __attribute__((visibility("default")))

namespace {

std::mutex g_init_mutex;
bool g_initialized = false;
thread_local std::string g_last_error;

struct Predictor {
  long pid;
  std::vector<std::vector<mx_uint>> out_shapes;  // cache for GetOutputShape
};

void set_error(const std::string &msg) { g_last_error = msg; }

std::string fetch_py_error() {
  PyObject *type = nullptr, *value = nullptr, *trace = nullptr;
  PyErr_Fetch(&type, &value, &trace);
  PyErr_NormalizeException(&type, &value, &trace);
  std::string out = "python error";
  if (value) {
    PyObject *s = PyObject_Str(value);
    if (s) {
      const char *c = PyUnicode_AsUTF8(s);
      if (c) out = c;
      Py_DECREF(s);
    }
  }
  Py_XDECREF(type);
  Py_XDECREF(value);
  Py_XDECREF(trace);
  return out;
}

// Initialize the interpreter + import the embed module once.
bool ensure_python() {
  std::lock_guard<std::mutex> lock(g_init_mutex);
  if (g_initialized) return true;
  if (!Py_IsInitialized()) {
    // the platform is CPU unless the deployer names one
    // (MXNET_PREDICT_PLATFORM): a chip belongs to one process, and an
    // embedded predictor must not claim one it was not given
    setenv("JAX_PLATFORMS", getenv("MXNET_PREDICT_PLATFORM")
                                 ? getenv("MXNET_PREDICT_PLATFORM")
                                 : "cpu",
           0);
    Py_InitializeEx(0);
    // release the GIL acquired by initialization: entry points each
    // take it via PyGILState_Ensure, and a held GIL here would
    // deadlock every OTHER thread's first call
    PyEval_SaveThread();
  }
  g_initialized = true;
  return true;
}

PyObject *embed_module() {
  PyObject *mod = PyImport_ImportModule("mxnet_tpu.predict_embed");
  if (!mod) set_error("cannot import mxnet_tpu.predict_embed: " +
                      fetch_py_error());
  return mod;
}

// call embed.<fn>(*args) -> new ref or nullptr (error recorded)
PyObject *call_embed(const char *fn, PyObject *args) {
  PyObject *mod = embed_module();
  if (!mod) return nullptr;
  PyObject *f = PyObject_GetAttrString(mod, fn);
  Py_DECREF(mod);
  if (!f) {
    set_error(std::string("missing embed function ") + fn);
    return nullptr;
  }
  PyObject *ret = PyObject_CallObject(f, args);
  Py_DECREF(f);
  if (!ret) set_error(fetch_py_error());
  return ret;
}

class GIL {
 public:
  GIL() { state_ = PyGILState_Ensure(); }
  ~GIL() { PyGILState_Release(state_); }

 private:
  PyGILState_STATE state_;
};

}  // namespace

MXTPU_API const char *MXGetLastError() { return g_last_error.c_str(); }

MXTPU_API int MXPredCreate(const char *symbol_json_str,
                           const void *param_bytes, int param_size,
                           int dev_type, int dev_id,
                           mx_uint num_input_nodes,
                           const char **input_keys,
                           const mx_uint *input_shape_indptr,
                           const mx_uint *input_shape_data,
                           PredictorHandle *out) {
  (void)dev_id;
  if (!ensure_python()) return -1;
  GIL gil;
  PyObject *names = PyTuple_New(num_input_nodes);
  PyObject *shapes = PyTuple_New(num_input_nodes);
  for (mx_uint i = 0; i < num_input_nodes; ++i) {
    PyTuple_SetItem(names, i, PyUnicode_FromString(input_keys[i]));
    mx_uint lo = input_shape_indptr[i], hi = input_shape_indptr[i + 1];
    PyObject *shape = PyTuple_New(hi - lo);
    for (mx_uint j = lo; j < hi; ++j)
      PyTuple_SetItem(shape, j - lo,
                      PyLong_FromUnsignedLong(input_shape_data[j]));
    PyTuple_SetItem(shapes, i, shape);
  }
  PyObject *args = Py_BuildValue(
      "(sy#iOO)", symbol_json_str, (const char *)param_bytes,
      (Py_ssize_t)param_size, dev_type, names, shapes);
  Py_DECREF(names);
  Py_DECREF(shapes);
  if (!args) {
    set_error(fetch_py_error());
    return -1;
  }
  PyObject *ret = call_embed("create", args);
  Py_DECREF(args);
  if (!ret) return -1;
  Predictor *p = new Predictor();
  p->pid = PyLong_AsLong(ret);
  Py_DECREF(ret);
  *out = p;
  return 0;
}

MXTPU_API int MXPredSetInput(PredictorHandle handle, const char *key,
                             const mx_float *data, mx_uint size) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  // shape is tracked python-side; pass the flat buffer and let the
  // embed module reshape to the declared input shape
  PyObject *mod = embed_module();
  if (!mod) return -1;
  PyObject *pred_map = PyObject_GetAttrString(mod, "_predictors");
  Py_DECREF(mod);
  if (!pred_map) {
    set_error("no predictor registry");
    return -1;
  }
  PyObject *pid = PyLong_FromLong(p->pid);
  PyObject *pobj = PyObject_GetItem(pred_map, pid);
  Py_DECREF(pred_map);
  Py_DECREF(pid);
  if (!pobj) {
    set_error("stale predictor handle");
    return -1;
  }
  PyObject *ishapes = PyObject_GetAttrString(pobj, "_input_shapes");
  Py_DECREF(pobj);
  if (!ishapes) {
    set_error("predictor missing input shapes");
    return -1;
  }
  PyObject *shape = PyMapping_GetItemString(ishapes, key);
  Py_DECREF(ishapes);
  if (!shape) {
    set_error(std::string("unknown input ") + key);
    PyErr_Clear();
    return -1;
  }
  PyObject *args = Py_BuildValue(
      "(lsy#O)", p->pid, key, (const char *)data,
      (Py_ssize_t)(size * sizeof(mx_float)), shape);
  Py_DECREF(shape);
  if (!args) {
    set_error(fetch_py_error());
    return -1;
  }
  PyObject *ret = call_embed("set_input", args);
  Py_DECREF(args);
  if (!ret) return -1;
  Py_DECREF(ret);
  return 0;
}

MXTPU_API int MXPredForward(PredictorHandle handle) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  PyObject *args = Py_BuildValue("(l)", p->pid);
  PyObject *ret = call_embed("forward", args);
  Py_DECREF(args);
  if (!ret) return -1;
  Py_DECREF(ret);
  return 0;
}

MXTPU_API int MXPredGetOutputShape(PredictorHandle handle, mx_uint index,
                                   mx_uint **shape_data,
                                   mx_uint *shape_ndim) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  PyObject *args = Py_BuildValue("(lI)", p->pid, index);
  PyObject *ret = call_embed("get_output_shape", args);
  Py_DECREF(args);
  if (!ret) return -1;
  Py_ssize_t n = PyTuple_Size(ret);
  if (p->out_shapes.size() <= index) p->out_shapes.resize(index + 1);
  p->out_shapes[index].resize(n);
  for (Py_ssize_t i = 0; i < n; ++i)
    p->out_shapes[index][i] =
        (mx_uint)PyLong_AsUnsignedLong(PyTuple_GetItem(ret, i));
  Py_DECREF(ret);
  *shape_data = p->out_shapes[index].data();
  *shape_ndim = (mx_uint)n;
  return 0;
}

MXTPU_API int MXPredGetOutput(PredictorHandle handle, mx_uint index,
                              mx_float *data, mx_uint size) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  PyObject *args = Py_BuildValue("(lI)", p->pid, index);
  PyObject *ret = call_embed("get_output", args);
  Py_DECREF(args);
  if (!ret) return -1;
  char *buf = nullptr;
  Py_ssize_t len = 0;
  if (PyBytes_AsStringAndSize(ret, &buf, &len) != 0) {
    Py_DECREF(ret);
    set_error(fetch_py_error());
    return -1;
  }
  if ((mx_uint)(len / sizeof(mx_float)) != size) {
    Py_DECREF(ret);
    set_error("output size mismatch");
    return -1;
  }
  std::memcpy(data, buf, len);
  Py_DECREF(ret);
  return 0;
}

MXTPU_API int MXPredReshape(mx_uint num_input_nodes,
                            const char **input_keys,
                            const mx_uint *input_shape_indptr,
                            const mx_uint *input_shape_data,
                            PredictorHandle handle, PredictorHandle *out) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  PyObject *names = PyTuple_New(num_input_nodes);
  PyObject *shapes = PyTuple_New(num_input_nodes);
  for (mx_uint i = 0; i < num_input_nodes; ++i) {
    PyTuple_SetItem(names, i, PyUnicode_FromString(input_keys[i]));
    mx_uint lo = input_shape_indptr[i], hi = input_shape_indptr[i + 1];
    PyObject *shape = PyTuple_New(hi - lo);
    for (mx_uint j = lo; j < hi; ++j)
      PyTuple_SetItem(shape, j - lo,
                      PyLong_FromUnsignedLong(input_shape_data[j]));
    PyTuple_SetItem(shapes, i, shape);
  }
  PyObject *args = Py_BuildValue("(lOO)", p->pid, names, shapes);
  Py_DECREF(names);
  Py_DECREF(shapes);
  PyObject *ret = call_embed("reshape", args);
  Py_DECREF(args);
  if (!ret) return -1;
  Py_DECREF(ret);
  *out = handle;  // reference reshapes into a NEW handle; same-handle
                  // rebinding is the jit-native equivalent (recompile
                  // is keyed by shape)
  return 0;
}

MXTPU_API int MXPredGetOutputType(PredictorHandle handle, mx_uint index,
                                  int *out_dtype) {
  (void)handle;
  (void)index;
  *out_dtype = 0;  // kFloat32: the ABI surface is float32 (GetOutput)
  return 0;
}

MXTPU_API int MXPredCreateEx(const char *symbol_json_str,
                             const void *param_bytes, int param_size,
                             int dev_type, int dev_id,
                             mx_uint num_input_nodes,
                             const char **input_keys,
                             const mx_uint *input_shape_indptr,
                             const mx_uint *input_shape_data,
                             mx_uint num_provided_arg_dtypes,
                             const char **provided_arg_dtype_names,
                             const int *provided_arg_dtypes,
                             PredictorHandle *out) {
  // dtype hints are an inference-time AMP feature in the reference; the
  // XLA program already runs the dtypes the symbol declares
  (void)num_provided_arg_dtypes;
  (void)provided_arg_dtype_names;
  (void)provided_arg_dtypes;
  return MXPredCreate(symbol_json_str, param_bytes, param_size, dev_type,
                      dev_id, num_input_nodes, input_keys,
                      input_shape_indptr, input_shape_data, out);
}

namespace {
struct NDList {
  long nid;
  // per-entry storage the C pointers point into
  std::vector<std::string> keys;
  std::vector<std::string> data;
  std::vector<std::vector<mx_uint>> shapes;
};
}  // namespace

MXTPU_API int MXNDListCreate(const char *nd_file_bytes, int nd_file_size,
                             NDListHandle *out, mx_uint *out_length) {
  if (!ensure_python()) return -1;
  GIL gil;
  PyObject *args = Py_BuildValue("(y#)", nd_file_bytes,
                                 (Py_ssize_t)nd_file_size);
  PyObject *ret = call_embed("ndlist_create", args);
  Py_DECREF(args);
  if (!ret) return -1;
  long nid = PyLong_AsLong(PyTuple_GetItem(ret, 0));
  long n = PyLong_AsLong(PyTuple_GetItem(ret, 1));
  Py_DECREF(ret);
  NDList *lst = new NDList();
  lst->nid = nid;
  lst->keys.resize(n);
  lst->data.resize(n);
  lst->shapes.resize(n);
  for (long i = 0; i < n; ++i) {
    PyObject *gargs = Py_BuildValue("(ll)", nid, i);
    PyObject *item = call_embed("ndlist_get", gargs);
    Py_DECREF(gargs);
    if (!item) {
      // release the python-side staging copies too, or they leak for
      // the process lifetime
      PyObject *fargs = Py_BuildValue("(l)", nid);
      PyObject *fr = call_embed("ndlist_free", fargs);
      Py_DECREF(fargs);
      Py_XDECREF(fr);
      delete lst;
      return -1;
    }
    lst->keys[i] = PyUnicode_AsUTF8(PyTuple_GetItem(item, 0));
    char *buf = nullptr;
    Py_ssize_t blen = 0;
    PyBytes_AsStringAndSize(PyTuple_GetItem(item, 1), &buf, &blen);
    lst->data[i].assign(buf, blen);
    PyObject *shape = PyTuple_GetItem(item, 2);
    Py_ssize_t nd = PyTuple_Size(shape);
    lst->shapes[i].resize(nd);
    for (Py_ssize_t d = 0; d < nd; ++d)
      lst->shapes[i][d] =
          (mx_uint)PyLong_AsUnsignedLong(PyTuple_GetItem(shape, d));
    Py_DECREF(item);
  }
  // the python-side copies are no longer needed
  PyObject *fargs = Py_BuildValue("(l)", nid);
  PyObject *fr = call_embed("ndlist_free", fargs);
  Py_DECREF(fargs);
  Py_XDECREF(fr);
  *out = lst;
  *out_length = (mx_uint)n;
  return 0;
}

MXTPU_API int MXNDListGet(NDListHandle handle, mx_uint index,
                          const char **out_key, const mx_float **out_data,
                          const mx_uint **out_shape, mx_uint *out_ndim) {
  NDList *lst = static_cast<NDList *>(handle);
  if (index >= lst->keys.size()) {
    set_error("NDList index out of range");
    return -1;
  }
  *out_key = lst->keys[index].c_str();
  *out_data = reinterpret_cast<const mx_float *>(lst->data[index].data());
  *out_shape = lst->shapes[index].data();
  *out_ndim = (mx_uint)lst->shapes[index].size();
  return 0;
}

MXTPU_API int MXNDListFree(NDListHandle handle) {
  delete static_cast<NDList *>(handle);
  return 0;
}

MXTPU_API int MXPredFree(PredictorHandle handle) {
  GIL gil;
  Predictor *p = static_cast<Predictor *>(handle);
  PyObject *args = Py_BuildValue("(l)", p->pid);
  PyObject *ret = call_embed("free", args);
  Py_DECREF(args);
  Py_XDECREF(ret);
  delete p;
  return ret ? 0 : -1;
}
