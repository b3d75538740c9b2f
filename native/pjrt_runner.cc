// C++ batch-inference runner over AOT-compiled XLA (StableHLO) programs,
// speaking the PJRT C API to any plugin (libtpu.so on TPU hosts; a mock
// plugin in tests).
//
// This is the TPU-native equivalent of the reference's JVM inference stack
// (reference: src/main/scala/com/yahoo/tensorflowonspark/TFModel.scala:24-29
// SavedModelBundle singleton; :245-292 feed/fetch via Session.runner), with
// the TF Java/JNI bridge replaced by PJRT: the runtime loads a serialized
// StableHLO module (produced by tensorflowonspark_tpu.aot.export_aot) plus a
// serialized CompileOptionsProto, compiles it on the plugin's device, and
// exposes a flat C ABI (create/compile/run/destroy) consumed by Python via
// ctypes and by the standalone CLI.
//
// Single-device by design: the pipeline layer shards data across executors
// (one runner per executor process), mirroring the reference's
// per-executor-JVM session cache.
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <string>
#include <vector>

#include <dlfcn.h>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

void set_err(char* err, int errlen, const std::string& msg) {
  if (err && errlen > 0) {
    std::snprintf(err, static_cast<size_t>(errlen), "%s", msg.c_str());
  }
}

// Converts a PJRT_Error (if any) to a message and frees it. Returns true if
// there was an error.
bool take_error(const PJRT_Api* api, PJRT_Error* e, char* err, int errlen) {
  if (e == nullptr) return false;
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = e;
  api->PJRT_Error_Message(&margs);
  set_err(err, errlen, std::string(margs.message, margs.message_size));
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = e;
  api->PJRT_Error_Destroy(&dargs);
  return true;
}

bool await_event(const PJRT_Api* api, PJRT_Event* ev, char* err, int errlen) {
  if (ev == nullptr) return true;
  PJRT_Event_Await_Args aargs;
  std::memset(&aargs, 0, sizeof(aargs));
  aargs.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aargs.event = ev;
  PJRT_Error* e = api->PJRT_Event_Await(&aargs);
  PJRT_Event_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  dargs.event = ev;
  api->PJRT_Event_Destroy(&dargs);
  return !take_error(api, e, err, errlen);
}

}  // namespace

extern "C" {

// Mirrors PJRT_Buffer_Type for the dtypes the data layer produces
// (PRED=1 S8=2 S16=3 S32=4 S64=5 U8=6 ... F16=10 F32=11 F64=12 BF16=13).
typedef struct {
  void* data;
  long long size_bytes;
  int dtype;
  int ndims;
  long long dims[8];
} tos_buffer;

typedef struct tos_runner {
  void* dl;
  const PJRT_Api* api;
  PJRT_Client* client;
  PJRT_Device* device;
  size_t num_devices;
  std::string platform;
} tos_runner;

typedef struct tos_exec {
  tos_runner* r;
  PJRT_LoadedExecutable* loaded;
  PJRT_Executable* exec;  // derived view, owned
  size_t num_outputs;
} tos_exec;

// Create-option marshalling: kinds 0 = string, 1 = int64.  Plugins like
// libtpu take no options; a proxying plugin may require them (its
// PJRT_Client_Create rejects an empty NamedValue list), so the extended
// entry point forwards key/value pairs as PJRT_NamedValues.
tos_runner* tos_runner_create_opts(const char* plugin_path,
                                   const char* const* opt_keys,
                                   const char* const* opt_str_vals,
                                   const long long* opt_int_vals,
                                   const int* opt_kinds, int n_opts,
                                   char* err, int errlen) {
  void* dl = dlopen(plugin_path, RTLD_NOW | RTLD_LOCAL);
  if (!dl) {
    set_err(err, errlen, std::string("dlopen failed: ") + dlerror());
    return nullptr;
  }
  using GetPjrtApiFn = const PJRT_Api* (*)();
  auto get_api = reinterpret_cast<GetPjrtApiFn>(dlsym(dl, "GetPjrtApi"));
  if (!get_api) {
    set_err(err, errlen, "plugin has no GetPjrtApi symbol");
    dlclose(dl);
    return nullptr;
  }
  const PJRT_Api* api = get_api();
  if (!api) {
    set_err(err, errlen, "GetPjrtApi returned null");
    dlclose(dl);
    return nullptr;
  }

  if (api->PJRT_Plugin_Initialize) {
    PJRT_Plugin_Initialize_Args iargs;
    std::memset(&iargs, 0, sizeof(iargs));
    iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    if (take_error(api, api->PJRT_Plugin_Initialize(&iargs), err, errlen)) {
      dlclose(dl);
      return nullptr;
    }
  }

  std::vector<PJRT_NamedValue> named(n_opts > 0 ? n_opts : 0);
  for (int i = 0; i < n_opts; ++i) {
    std::memset(&named[i], 0, sizeof(PJRT_NamedValue));
    named[i].struct_size = PJRT_NamedValue_STRUCT_SIZE;
    named[i].name = opt_keys[i];
    named[i].name_size = std::strlen(opt_keys[i]);
    if (opt_kinds[i] == 0) {
      named[i].type = PJRT_NamedValue_kString;
      named[i].string_value = opt_str_vals[i];
      named[i].value_size = std::strlen(opt_str_vals[i]);
    } else {
      named[i].type = PJRT_NamedValue_kInt64;
      named[i].int64_value = static_cast<int64_t>(opt_int_vals[i]);
      named[i].value_size = 1;
    }
  }

  PJRT_Client_Create_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
  if (n_opts > 0) {
    cargs.create_options = named.data();
    cargs.num_options = static_cast<size_t>(n_opts);
  }
  if (take_error(api, api->PJRT_Client_Create(&cargs), err, errlen)) {
    dlclose(dl);
    return nullptr;
  }
  // Once a client exists, failure paths destroy it but keep the plugin
  // loaded: its background threads may outlive the client, and dlclosing a
  // library with live threads is undefined behavior (same reason
  // tos_runner_destroy never dlcloses).
  auto fail_with_client = [&]() {
    PJRT_Client_Destroy_Args xargs;
    std::memset(&xargs, 0, sizeof(xargs));
    xargs.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    xargs.client = cargs.client;
    api->PJRT_Client_Destroy(&xargs);
  };

  PJRT_Client_AddressableDevices_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
  dargs.client = cargs.client;
  if (take_error(api, api->PJRT_Client_AddressableDevices(&dargs), err,
                 errlen)) {
    fail_with_client();
    return nullptr;
  }
  if (dargs.num_addressable_devices == 0) {
    set_err(err, errlen, "no addressable devices");
    fail_with_client();
    return nullptr;
  }

  PJRT_Client_PlatformName_Args pargs;
  std::memset(&pargs, 0, sizeof(pargs));
  pargs.struct_size = PJRT_Client_PlatformName_Args_STRUCT_SIZE;
  pargs.client = cargs.client;
  std::string platform = "unknown";
  if (!take_error(api, api->PJRT_Client_PlatformName(&pargs), err, errlen)) {
    platform.assign(pargs.platform_name, pargs.platform_name_size);
  }

  auto* r = new tos_runner();
  r->dl = dl;
  r->api = api;
  r->client = cargs.client;
  r->device = dargs.addressable_devices[0];
  r->num_devices = dargs.num_addressable_devices;
  r->platform = platform;
  return r;
}

tos_runner* tos_runner_create(const char* plugin_path, char* err,
                              int errlen) {
  return tos_runner_create_opts(plugin_path, nullptr, nullptr, nullptr,
                                nullptr, 0, err, errlen);
}

void tos_runner_destroy(tos_runner* r) {
  if (!r) return;
  if (r->client) {
    PJRT_Client_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
    args.client = r->client;
    r->api->PJRT_Client_Destroy(&args);
  }
  // Keep the plugin loaded: some PJRT plugins register process-global state
  // that does not survive dlclose + reopen.
  delete r;
}

int tos_runner_device_count(tos_runner* r) {
  return r ? static_cast<int>(r->num_devices) : 0;
}

const char* tos_runner_platform(tos_runner* r) {
  return r ? r->platform.c_str() : "";
}

tos_exec* tos_runner_compile(tos_runner* r, const char* mlir, long long mlir_len,
                             const char* copts, long long copts_len, char* err,
                             int errlen) {
  static const char kFormat[] = "mlir";
  PJRT_Program program;
  std::memset(&program, 0, sizeof(program));
  program.struct_size = PJRT_Program_STRUCT_SIZE;
  program.code = const_cast<char*>(mlir);
  program.code_size = static_cast<size_t>(mlir_len);
  program.format = kFormat;
  program.format_size = sizeof(kFormat) - 1;

  PJRT_Client_Compile_Args cargs;
  std::memset(&cargs, 0, sizeof(cargs));
  cargs.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
  cargs.client = r->client;
  cargs.program = &program;
  cargs.compile_options = copts;
  cargs.compile_options_size = static_cast<size_t>(copts_len);
  if (take_error(r->api, r->api->PJRT_Client_Compile(&cargs), err, errlen)) {
    return nullptr;
  }

  PJRT_LoadedExecutable_GetExecutable_Args gargs;
  std::memset(&gargs, 0, sizeof(gargs));
  gargs.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
  gargs.loaded_executable = cargs.executable;
  if (take_error(r->api, r->api->PJRT_LoadedExecutable_GetExecutable(&gargs),
                 err, errlen)) {
    return nullptr;
  }

  PJRT_Executable_NumOutputs_Args nargs;
  std::memset(&nargs, 0, sizeof(nargs));
  nargs.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
  nargs.executable = gargs.executable;
  if (take_error(r->api, r->api->PJRT_Executable_NumOutputs(&nargs), err,
                 errlen)) {
    return nullptr;
  }

  auto* x = new tos_exec();
  x->r = r;
  x->loaded = cargs.executable;
  x->exec = gargs.executable;
  x->num_outputs = nargs.num_outputs;
  return x;
}

int tos_exec_num_outputs(tos_exec* x) {
  return x ? static_cast<int>(x->num_outputs) : -1;
}

void tos_exec_destroy(tos_exec* x) {
  if (!x) return;
  const PJRT_Api* api = x->r->api;
  if (x->exec) {
    PJRT_Executable_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Executable_Destroy_Args_STRUCT_SIZE;
    args.executable = x->exec;
    api->PJRT_Executable_Destroy(&args);
  }
  if (x->loaded) {
    PJRT_LoadedExecutable_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
    args.executable = x->loaded;
    api->PJRT_LoadedExecutable_Destroy(&args);
  }
  delete x;
}

void tos_free(void* p) { std::free(p); }

// Runs one batch: host inputs -> device -> execute -> host outputs.
// outs[i].data is malloc'd by the runner; caller frees via tos_free.
int tos_exec_run(tos_exec* x, const tos_buffer* ins, int n_in, tos_buffer* outs,
                 int max_out, int* n_out, char* err, int errlen) {
  const PJRT_Api* api = x->r->api;
  if (static_cast<size_t>(max_out) < x->num_outputs) {
    set_err(err, errlen, "max_out too small for executable outputs");
    return -1;
  }

  std::vector<PJRT_Buffer*> in_bufs;
  in_bufs.reserve(static_cast<size_t>(n_in));
  auto cleanup_inputs = [&]() {
    for (PJRT_Buffer* b : in_bufs) {
      PJRT_Buffer_Destroy_Args args;
      std::memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
      args.buffer = b;
      api->PJRT_Buffer_Destroy(&args);
    }
  };

  for (int i = 0; i < n_in; ++i) {
    std::vector<int64_t> dims(ins[i].dims, ins[i].dims + ins[i].ndims);
    PJRT_Client_BufferFromHostBuffer_Args bargs;
    std::memset(&bargs, 0, sizeof(bargs));
    bargs.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    bargs.client = x->r->client;
    bargs.data = ins[i].data;
    bargs.type = static_cast<PJRT_Buffer_Type>(ins[i].dtype);
    bargs.dims = dims.data();
    bargs.num_dims = dims.size();
    bargs.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    bargs.device = x->r->device;
    if (take_error(api, api->PJRT_Client_BufferFromHostBuffer(&bargs), err,
                   errlen)) {
      cleanup_inputs();
      return -1;
    }
    in_bufs.push_back(bargs.buffer);
    if (!await_event(api, bargs.done_with_host_buffer, err, errlen)) {
      cleanup_inputs();
      return -1;
    }
  }

  std::vector<PJRT_Buffer*> out_bufs(x->num_outputs, nullptr);
  PJRT_Buffer** out_list = out_bufs.data();
  PJRT_Buffer* const* arg_list = in_bufs.data();
  PJRT_Event* done = nullptr;

  PJRT_ExecuteOptions opts;
  std::memset(&opts, 0, sizeof(opts));
  opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;

  PJRT_LoadedExecutable_Execute_Args eargs;
  std::memset(&eargs, 0, sizeof(eargs));
  eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
  eargs.executable = x->loaded;
  eargs.options = &opts;
  eargs.argument_lists = &arg_list;
  eargs.num_devices = 1;
  eargs.num_args = static_cast<size_t>(n_in);
  eargs.output_lists = &out_list;
  eargs.device_complete_events = &done;
  eargs.execute_device = x->r->device;
  if (take_error(api, api->PJRT_LoadedExecutable_Execute(&eargs), err,
                 errlen)) {
    cleanup_inputs();
    return -1;
  }
  bool exec_ok = await_event(api, done, err, errlen);
  cleanup_inputs();

  auto cleanup_outputs = [&](size_t upto_host) {
    for (size_t i = 0; i < x->num_outputs; ++i) {
      if (i < upto_host && outs[i].data) {
        std::free(outs[i].data);
        outs[i].data = nullptr;
      }
      if (out_bufs[i]) {
        PJRT_Buffer_Destroy_Args args;
        std::memset(&args, 0, sizeof(args));
        args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        args.buffer = out_bufs[i];
        api->PJRT_Buffer_Destroy(&args);
      }
    }
  };
  if (!exec_ok) {
    cleanup_outputs(0);
    return -1;
  }

  for (size_t i = 0; i < x->num_outputs; ++i) {
    PJRT_Buffer_Dimensions_Args dargs;
    std::memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    dargs.buffer = out_bufs[i];
    PJRT_Buffer_ElementType_Args targs;
    std::memset(&targs, 0, sizeof(targs));
    targs.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    targs.buffer = out_bufs[i];
    if (take_error(api, api->PJRT_Buffer_Dimensions(&dargs), err, errlen) ||
        take_error(api, api->PJRT_Buffer_ElementType(&targs), err, errlen) ||
        dargs.num_dims > 8) {
      if (dargs.num_dims > 8) set_err(err, errlen, "output rank > 8");
      cleanup_outputs(i);
      return -1;
    }

    // Request an explicit DENSE ROW-MAJOR host layout: with host_layout
    // null, PJRT copies in the SOURCE buffer's layout — on real TPUs the
    // compiler may pick a non-row-major device layout (observed on a
    // [64, 10] output: column-major, i.e. the host saw a transposed
    // array), and only the mock/CPU paths happen to match row-major.
    std::vector<int64_t> m2m(dargs.num_dims);
    for (size_t d = 0; d < dargs.num_dims; ++d) {
      m2m[d] = static_cast<int64_t>(dargs.num_dims - 1 - d);
    }
    PJRT_Buffer_MemoryLayout row_major;
    std::memset(&row_major, 0, sizeof(row_major));
    row_major.struct_size = PJRT_Buffer_MemoryLayout_STRUCT_SIZE;
    row_major.type = PJRT_Buffer_MemoryLayout_Type_Tiled;
    row_major.tiled.struct_size = PJRT_Buffer_MemoryLayout_Tiled_STRUCT_SIZE;
    row_major.tiled.minor_to_major = m2m.data();
    row_major.tiled.minor_to_major_size = dargs.num_dims;

    PJRT_Buffer_ToHostBuffer_Args hargs;
    std::memset(&hargs, 0, sizeof(hargs));
    hargs.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    hargs.src = out_bufs[i];
    hargs.host_layout = &row_major;
    hargs.dst = nullptr;  // size query
    if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&hargs), err, errlen)) {
      cleanup_outputs(i);
      return -1;
    }
    void* host = std::malloc(hargs.dst_size ? hargs.dst_size : 1);
    hargs.dst = host;
    hargs.event = nullptr;
    if (take_error(api, api->PJRT_Buffer_ToHostBuffer(&hargs), err, errlen) ||
        !await_event(api, hargs.event, err, errlen)) {
      std::free(host);
      cleanup_outputs(i);
      return -1;
    }

    outs[i].data = host;
    outs[i].size_bytes = static_cast<long long>(hargs.dst_size);
    outs[i].dtype = static_cast<int>(targs.type);
    outs[i].ndims = static_cast<int>(dargs.num_dims);
    for (size_t d = 0; d < dargs.num_dims; ++d) {
      outs[i].dims[d] = dargs.dims[d];
    }
  }
  for (size_t i = 0; i < x->num_outputs; ++i) {
    PJRT_Buffer_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = out_bufs[i];
    api->PJRT_Buffer_Destroy(&args);
  }
  *n_out = static_cast<int>(x->num_outputs);
  return 0;
}

}  // extern "C"
