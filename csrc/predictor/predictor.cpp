// Native inference predictor: loads a paddle_tpu jit.save artifact and
// executes it through the PJRT C API of any PJRT plugin (libtpu, or any
// GetPjrtApi-exporting .so).
//
// Reference role: paddle/fluid/inference/api/analysis_predictor.cc:1665 —
// the C++ serving engine around the saved inference artifact.  The
// TPU-native translation: the artifact's program is StableHLO
// (<path>.pdstablehlo, written by paddle_tpu.jit.save), parameters are an
// uncompressed .npz (<path>.pdiparams.npz), and the runtime is PJRT —
// create client, compile, upload params once, execute per request.
//
// Exposed as a small C ABI for the ctypes binding
// (paddle_tpu/inference/native.py).  C++17, deps: libdl only (the PJRT C
// API header is a self-contained C header from the installed XLA).

#include <dlfcn.h>

#include <cstdint>
#include <cstring>
#include <fstream>
#include <memory>
#include <sstream>
#include <string>
#include <vector>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

// ---------------------------------------------------------------- helpers

thread_local std::string g_last_error;

void set_error(const std::string& msg) { g_last_error = msg; }

std::string pjrt_error_message(const PJRT_Api* api, PJRT_Error* err) {
  PJRT_Error_Message_Args margs;
  std::memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  api->PJRT_Error_Message(&margs);
  std::string msg(margs.message, margs.message_size);
  PJRT_Error_Destroy_Args dargs;
  std::memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  api->PJRT_Error_Destroy(&dargs);
  return msg;
}

#define PJRT_CHECK(api, call)                                   \
  do {                                                          \
    PJRT_Error* _err = (call);                                  \
    if (_err != nullptr) {                                      \
      set_error(#call ": " + pjrt_error_message((api), _err));  \
      return false;                                             \
    }                                                           \
  } while (0)

bool read_file(const std::string& path, std::string* out) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    set_error("cannot open " + path);
    return false;
  }
  std::ostringstream ss;
  ss << f.rdbuf();
  *out = ss.str();
  return true;
}

// ------------------------------------------------- minimal npz/npy reader
// np.savez writes a ZIP archive with STORED (uncompressed) .npy members.

struct NpyArray {
  std::string name;                 // member name without ".npy"
  std::string dtype;                // numpy descr, e.g. "<f4"
  std::vector<int64_t> shape;
  const char* data = nullptr;       // points into the archive buffer
  size_t nbytes = 0;
};

uint16_t rd16(const char* p) {
  uint16_t v;
  std::memcpy(&v, p, 2);
  return v;
}
uint32_t rd32(const char* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}

bool parse_npy(const char* p, size_t n, NpyArray* out) {
  if (n < 10 || std::memcmp(p, "\x93NUMPY", 6) != 0) {
    set_error("bad npy magic");
    return false;
  }
  int major = p[6];
  size_t hlen, hoff;
  if (major == 1) {
    hlen = rd16(p + 8);
    hoff = 10;
  } else {
    hlen = rd32(p + 8);
    hoff = 12;
  }
  std::string header(p + hoff, hlen);
  // header is a python dict literal: {'descr': '<f4', 'fortran_order':
  // False, 'shape': (3, 4), }
  auto find_val = [&](const std::string& key) -> std::string {
    size_t k = header.find("'" + key + "'");
    if (k == std::string::npos) return "";
    size_t c = header.find(':', k);
    size_t start = header.find_first_not_of(" ", c + 1);
    size_t end = start;
    if (header[start] == '\'') {
      end = header.find('\'', start + 1) + 1;
    } else if (header[start] == '(') {
      end = header.find(')', start) + 1;
    } else {
      end = header.find_first_of(",}", start);
    }
    return header.substr(start, end - start);
  };
  if (find_val("fortran_order") != "False") {
    set_error("fortran_order arrays unsupported");
    return false;
  }
  std::string descr = find_val("descr");
  out->dtype = descr.substr(1, descr.size() - 2);  // strip quotes
  if (!out->dtype.empty() && (out->dtype[0] == '<' || out->dtype[0] == '>' ||
                              out->dtype[0] == '=' || out->dtype[0] == '|')) {
    if (out->dtype[0] == '>') {
      set_error("big-endian npy arrays unsupported");
      return false;
    }
    out->dtype = out->dtype.substr(1);
  }
  std::string shape = find_val("shape");           // "(3, 4)" or "()"
  out->shape.clear();
  for (size_t i = 1; i < shape.size();) {
    if (isdigit(shape[i])) {
      size_t j = i;
      while (j < shape.size() && isdigit(shape[j])) j++;
      out->shape.push_back(std::stoll(shape.substr(i, j - i)));
      i = j;
    } else {
      i++;
    }
  }
  out->data = p + hoff + hlen;
  out->nbytes = n - hoff - hlen;
  return true;
}

bool parse_npz(const std::string& buf, std::vector<NpyArray>* arrays) {
  // walk the central directory (local headers may use data descriptors, so
  // their size fields can be zero — numpy writes them that way)
  size_t eocd = std::string::npos;
  for (size_t i = buf.size() >= 22 ? buf.size() - 22 : 0; i + 4 <= buf.size();
       i--) {
    if (rd32(buf.data() + i) == 0x06054b50) {
      eocd = i;
      break;
    }
    if (i == 0) break;
  }
  if (eocd == std::string::npos) {
    set_error("npz: no zip end-of-central-directory record");
    return false;
  }
  uint16_t n_entries = rd16(buf.data() + eocd + 10);
  uint32_t cd_off = rd32(buf.data() + eocd + 16);
  if (cd_off == 0xFFFFFFFFu || n_entries == 0xFFFFu) {
    set_error("zip64 npz archives (>4GB or >65535 members) unsupported by "
              "the native predictor; shard the params");
    return false;
  }
  size_t off = cd_off;
  for (uint16_t e = 0; e < n_entries; e++) {
    if (off + 46 > buf.size() || rd32(buf.data() + off) != 0x02014b50) {
      set_error("npz: bad central directory entry");
      return false;
    }
    uint16_t method = rd16(buf.data() + off + 10);
    uint32_t csize = rd32(buf.data() + off + 20);
    uint16_t nlen = rd16(buf.data() + off + 28);
    uint16_t elen = rd16(buf.data() + off + 30);
    uint16_t clen = rd16(buf.data() + off + 32);
    uint32_t lho = rd32(buf.data() + off + 42);
    std::string name(buf.data() + off + 46, nlen);
    off += 46 + nlen + elen + clen;
    if (method != 0) {
      set_error("npz member " + name + " is compressed; expected "
                "np.savez (uncompressed)");
      return false;
    }
    // local header gives the true data offset (its name/extra lengths can
    // differ from the central entry's)
    uint16_t lh_nlen = rd16(buf.data() + lho + 26);
    uint16_t lh_elen = rd16(buf.data() + lho + 28);
    const char* data = buf.data() + lho + 30 + lh_nlen + lh_elen;
    NpyArray arr;
    if (!parse_npy(data, csize, &arr)) return false;
    if (name.size() > 4 && name.substr(name.size() - 4) == ".npy")
      name = name.substr(0, name.size() - 4);
    arr.name = name;
    arrays->push_back(arr);
  }
  if (arrays->empty()) {
    set_error("no npy members found in npz");
    return false;
  }
  return true;
}

// dtype descr -> PJRT type + element size
bool dtype_to_pjrt(const std::string& d, PJRT_Buffer_Type* t, size_t* size) {
  if (d == "f4") { *t = PJRT_Buffer_Type_F32; *size = 4; return true; }
  if (d == "f8") { *t = PJRT_Buffer_Type_F64; *size = 8; return true; }
  if (d == "f2") { *t = PJRT_Buffer_Type_F16; *size = 2; return true; }
  if (d == "i4") { *t = PJRT_Buffer_Type_S32; *size = 4; return true; }
  if (d == "i8") { *t = PJRT_Buffer_Type_S64; *size = 8; return true; }
  if (d == "i1") { *t = PJRT_Buffer_Type_S8;  *size = 1; return true; }
  if (d == "u1") { *t = PJRT_Buffer_Type_U8;  *size = 1; return true; }
  if (d == "u4") { *t = PJRT_Buffer_Type_U32; *size = 4; return true; }
  if (d == "u8") { *t = PJRT_Buffer_Type_U64; *size = 8; return true; }
  if (d == "b1") { *t = PJRT_Buffer_Type_PRED; *size = 1; return true; }
  if (d == "V2" || d == "bfloat16") {
    *t = PJRT_Buffer_Type_BF16; *size = 2; return true;
  }
  set_error("unsupported dtype descr " + d);
  return false;
}

// predictor.py dtype codes (keep in sync with inference/native.py)
bool code_to_pjrt(int code, PJRT_Buffer_Type* t, size_t* size) {
  switch (code) {
    case 0: *t = PJRT_Buffer_Type_F32; *size = 4; return true;
    case 1: *t = PJRT_Buffer_Type_F64; *size = 8; return true;
    case 2: *t = PJRT_Buffer_Type_S32; *size = 4; return true;
    case 3: *t = PJRT_Buffer_Type_S64; *size = 8; return true;
    case 4: *t = PJRT_Buffer_Type_BF16; *size = 2; return true;
    case 5: *t = PJRT_Buffer_Type_PRED; *size = 1; return true;
    case 6: *t = PJRT_Buffer_Type_U8; *size = 1; return true;
    case 7: *t = PJRT_Buffer_Type_S8; *size = 1; return true;
  }
  set_error("bad dtype code " + std::to_string(code));
  return false;
}

int pjrt_to_code(PJRT_Buffer_Type t) {
  switch (t) {
    case PJRT_Buffer_Type_F32: return 0;
    case PJRT_Buffer_Type_F64: return 1;
    case PJRT_Buffer_Type_S32: return 2;
    case PJRT_Buffer_Type_S64: return 3;
    case PJRT_Buffer_Type_BF16: return 4;
    case PJRT_Buffer_Type_PRED: return 5;
    case PJRT_Buffer_Type_U8: return 6;
    case PJRT_Buffer_Type_S8: return 7;
    case PJRT_Buffer_Type_F16: return 8;
    case PJRT_Buffer_Type_U16: return 9;
    case PJRT_Buffer_Type_S16: return 10;
    case PJRT_Buffer_Type_U32: return 11;
    case PJRT_Buffer_Type_U64: return 12;
    default: return -1;
  }
}

// extract ["a", "b", ...] for a key from the tiny .pdmeta json we write
std::vector<std::string> json_string_array(const std::string& js,
                                           const std::string& key) {
  std::vector<std::string> out;
  size_t k = js.find("\"" + key + "\"");
  if (k == std::string::npos) return out;
  size_t lb = js.find('[', k);
  size_t rb = js.find(']', lb);
  size_t i = lb;
  while (true) {
    size_t q1 = js.find('"', i + 1);
    if (q1 == std::string::npos || q1 > rb) break;
    size_t q2 = js.find('"', q1 + 1);
    out.push_back(js.substr(q1 + 1, q2 - q1 - 1));
    i = q2;
  }
  return out;
}

// ------------------------------------------------------------- predictor

struct Predictor {
  void* dl = nullptr;
  const PJRT_Api* api = nullptr;
  PJRT_Client* client = nullptr;
  PJRT_Device* device = nullptr;
  PJRT_LoadedExecutable* exec = nullptr;
  size_t num_params = 0;
  size_t num_outputs = 0;
  std::vector<PJRT_Buffer*> param_bufs;   // uploaded once
  std::vector<PJRT_Buffer*> out_bufs;     // last run's outputs

  bool await_event(PJRT_Event* ev) {
    PJRT_Event_Await_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
    args.event = ev;
    PJRT_Error* err = api->PJRT_Event_Await(&args);
    PJRT_Event_Destroy_Args dargs;
    std::memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
    dargs.event = ev;
    api->PJRT_Event_Destroy(&dargs);
    if (err) {
      set_error("event await: " + pjrt_error_message(api, err));
      return false;
    }
    return true;
  }

  bool host_to_device(const void* data, PJRT_Buffer_Type type,
                      const int64_t* dims, size_t ndims, PJRT_Buffer** out) {
    PJRT_Client_BufferFromHostBuffer_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    args.client = client;
    args.data = data;
    args.type = type;
    args.dims = dims;
    args.num_dims = ndims;
    args.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    args.device = device;
    PJRT_CHECK(api, api->PJRT_Client_BufferFromHostBuffer(&args));
    if (!await_event(args.done_with_host_buffer)) return false;
    *out = args.buffer;
    return true;
  }

  void destroy_buffer(PJRT_Buffer* b) {
    if (!b) return;
    PJRT_Buffer_Destroy_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
    args.buffer = b;
    api->PJRT_Buffer_Destroy(&args);
  }

  bool init(const std::string& model_path, const std::string& plugin_path,
            const std::string& options) {
    dl = dlopen(plugin_path.c_str(), RTLD_NOW | RTLD_LOCAL);
    if (!dl) {
      set_error(std::string("dlopen failed: ") + dlerror());
      return false;
    }
    using GetApiFn = const PJRT_Api* (*)();
    auto get_api = reinterpret_cast<GetApiFn>(dlsym(dl, "GetPjrtApi"));
    if (!get_api) {
      set_error("plugin has no GetPjrtApi symbol");
      return false;
    }
    api = get_api();

    PJRT_Plugin_Initialize_Args iargs;
    std::memset(&iargs, 0, sizeof(iargs));
    iargs.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    PJRT_CHECK(api, api->PJRT_Plugin_Initialize(&iargs));

    // create_options: "key=value;key=value" — integer-looking values map
    // to kInt64, everything else to kString (matches what jax's
    // register_plugin(options=...) passes for e.g. the libtpu plugin:
    // topology, session_id, rank, ...)
    std::vector<std::pair<std::string, std::string>> kv;
    for (size_t i = 0; i < options.size();) {
      size_t semi = options.find(';', i);
      if (semi == std::string::npos) semi = options.size();
      std::string pair = options.substr(i, semi - i);
      size_t eq = pair.find('=');
      if (eq != std::string::npos)
        kv.emplace_back(pair.substr(0, eq), pair.substr(eq + 1));
      i = semi + 1;
    }
    std::vector<PJRT_NamedValue> named(kv.size());
    std::vector<int64_t> int_store(kv.size());
    for (size_t i = 0; i < kv.size(); i++) {
      PJRT_NamedValue& nv = named[i];
      std::memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = kv[i].first.c_str();
      nv.name_size = kv[i].first.size();
      const std::string& v = kv[i].second;
      size_t digits_from = (v.size() > 1 && v[0] == '-') ? 1 : 0;
      bool is_int = v.size() > digits_from &&
          v.find_first_not_of("0123456789", digits_from) ==
              std::string::npos;
      if (is_int) {
        try {
          int_store[i] = std::stoll(v);
        } catch (const std::exception&) {
          set_error("bad integer option value '" + v + "' for key '" +
                    kv[i].first + "'");
          return false;
        }
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = int_store[i];
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = v.c_str();
        nv.value_size = v.size();
      }
    }

    PJRT_Client_Create_Args cargs;
    std::memset(&cargs, 0, sizeof(cargs));
    cargs.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    cargs.create_options = named.empty() ? nullptr : named.data();
    cargs.num_options = named.size();
    PJRT_CHECK(api, api->PJRT_Client_Create(&cargs));
    client = cargs.client;

    PJRT_Client_AddressableDevices_Args devargs;
    std::memset(&devargs, 0, sizeof(devargs));
    devargs.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    devargs.client = client;
    PJRT_CHECK(api, api->PJRT_Client_AddressableDevices(&devargs));
    if (devargs.num_addressable_devices == 0) {
      set_error("plugin reports no addressable devices");
      return false;
    }
    device = devargs.addressable_devices[0];

    // program: StableHLO text written by jit.save
    std::string mlir;
    if (!read_file(model_path + ".pdstablehlo", &mlir)) return false;

    PJRT_Program program;
    std::memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = mlir.data();
    program.code_size = mlir.size();
    program.format = "mlir";
    program.format_size = 4;

    // minimal CompileOptionsProto: executable_build_options(field 3) with
    // num_replicas(4)=1, num_partitions(5)=1
    static const char kOptions[] = {0x1a, 0x04, 0x20, 0x01, 0x28, 0x01};

    PJRT_Client_Compile_Args comp;
    std::memset(&comp, 0, sizeof(comp));
    comp.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    comp.client = client;
    comp.program = &program;
    comp.compile_options = kOptions;
    comp.compile_options_size = sizeof(kOptions);
    PJRT_CHECK(api, api->PJRT_Client_Compile(&comp));
    exec = comp.executable;

    PJRT_LoadedExecutable_GetExecutable_Args ge;
    std::memset(&ge, 0, sizeof(ge));
    ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    ge.loaded_executable = exec;
    PJRT_CHECK(api, api->PJRT_LoadedExecutable_GetExecutable(&ge));
    PJRT_Executable_NumOutputs_Args no;
    std::memset(&no, 0, sizeof(no));
    no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    no.executable = ge.executable;
    PJRT_CHECK(api, api->PJRT_Executable_NumOutputs(&no));
    num_outputs = no.num_outputs;

    // parameters: ordered by .pdmeta param_names, uploaded once
    std::string meta;
    if (!read_file(model_path + ".pdmeta", &meta)) return false;
    std::vector<std::string> names = json_string_array(meta, "param_names");

    std::string npz;
    if (!read_file(model_path + ".pdiparams.npz", &npz)) return false;
    params_archive_ = std::move(npz);  // buffers point into this
    std::vector<NpyArray> arrays;
    if (!parse_npz(params_archive_, &arrays)) return false;

    for (const auto& name : names) {
      const NpyArray* found = nullptr;
      for (const auto& a : arrays)
        if (a.name == name) { found = &a; break; }
      if (!found) {
        set_error("param " + name + " missing from npz");
        return false;
      }
      PJRT_Buffer_Type t;
      size_t esize;
      if (!dtype_to_pjrt(found->dtype, &t, &esize)) return false;
      PJRT_Buffer* buf = nullptr;
      if (!host_to_device(found->data, t, found->shape.data(),
                          found->shape.size(), &buf))
        return false;
      param_bufs.push_back(buf);
    }
    num_params = param_bufs.size();
    return true;
  }

  bool run(int num_inputs, void** in_data, const int64_t* in_dims_flat,
           const int* in_ndims, const int* in_dtypes) {
    for (auto* b : out_bufs) destroy_buffer(b);
    out_bufs.clear();

    std::vector<PJRT_Buffer*> input_bufs;
    size_t dim_off = 0;
    bool ok = true;
    for (int i = 0; i < num_inputs && ok; i++) {
      PJRT_Buffer_Type t;
      size_t esize;
      if (!code_to_pjrt(in_dtypes[i], &t, &esize)) { ok = false; break; }
      PJRT_Buffer* buf = nullptr;
      ok = host_to_device(in_data[i], t, in_dims_flat + dim_off,
                          in_ndims[i], &buf);
      dim_off += in_ndims[i];
      if (ok) input_bufs.push_back(buf);
    }

    if (ok) {
      std::vector<PJRT_Buffer*> all_args(param_bufs);
      all_args.insert(all_args.end(), input_bufs.begin(), input_bufs.end());
      PJRT_Buffer* const* arg_list = all_args.data();

      std::vector<PJRT_Buffer*> outs(num_outputs, nullptr);
      PJRT_Buffer** out_list = outs.data();
      PJRT_Event* done = nullptr;

      PJRT_ExecuteOptions opts;
      std::memset(&opts, 0, sizeof(opts));
      opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
      // params must not be donated: they are reused across run() calls
      std::vector<int64_t> non_donatable(num_params);
      for (size_t i = 0; i < num_params; i++) non_donatable[i] = i;
      opts.non_donatable_input_indices = non_donatable.data();
      opts.num_non_donatable_input_indices = non_donatable.size();

      PJRT_LoadedExecutable_Execute_Args eargs;
      std::memset(&eargs, 0, sizeof(eargs));
      eargs.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
      eargs.executable = exec;
      eargs.options = &opts;
      eargs.argument_lists = &arg_list;
      eargs.num_devices = 1;
      eargs.num_args = all_args.size();
      eargs.output_lists = &out_list;
      eargs.device_complete_events = &done;
      PJRT_Error* err = api->PJRT_LoadedExecutable_Execute(&eargs);
      if (err) {
        set_error("execute: " + pjrt_error_message(api, err));
        ok = false;
      } else {
        ok = await_event(done);
        out_bufs.assign(outs.begin(), outs.end());
      }
    }
    for (auto* b : input_bufs) destroy_buffer(b);
    return ok;
  }

  bool output_info(int i, int64_t* dims, int max_dims, int* ndims,
                   int* dtype_code) {
    PJRT_Buffer* b = out_bufs.at(i);
    PJRT_Buffer_Dimensions_Args dargs;
    std::memset(&dargs, 0, sizeof(dargs));
    dargs.struct_size = PJRT_Buffer_Dimensions_Args_STRUCT_SIZE;
    dargs.buffer = b;
    PJRT_CHECK(api, api->PJRT_Buffer_Dimensions(&dargs));
    if (dargs.num_dims > static_cast<size_t>(max_dims)) {
      set_error("output rank " + std::to_string(dargs.num_dims) +
                " exceeds caller capacity " + std::to_string(max_dims));
      return false;
    }
    *ndims = static_cast<int>(dargs.num_dims);
    for (size_t d = 0; d < dargs.num_dims; d++) dims[d] = dargs.dims[d];
    PJRT_Buffer_ElementType_Args targs;
    std::memset(&targs, 0, sizeof(targs));
    targs.struct_size = PJRT_Buffer_ElementType_Args_STRUCT_SIZE;
    targs.buffer = b;
    PJRT_CHECK(api, api->PJRT_Buffer_ElementType(&targs));
    *dtype_code = pjrt_to_code(targs.type);
    if (*dtype_code < 0) {
      set_error("unsupported output element type " +
                std::to_string(static_cast<int>(targs.type)));
      return false;
    }
    return true;
  }

  bool output_copy(int i, void* dst, size_t dst_size) {
    PJRT_Buffer_ToHostBuffer_Args args;
    std::memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    args.src = out_bufs.at(i);
    args.dst = dst;
    args.dst_size = dst_size;
    PJRT_CHECK(api, api->PJRT_Buffer_ToHostBuffer(&args));
    return await_event(args.event);
  }

  ~Predictor() {
    for (auto* b : out_bufs) destroy_buffer(b);
    if (!owner) return;  // clones share client/exec/params with the owner
    for (auto* b : param_bufs) destroy_buffer(b);
    if (exec) {
      PJRT_LoadedExecutable_Destroy_Args args;
      std::memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_LoadedExecutable_Destroy_Args_STRUCT_SIZE;
      args.executable = exec;
      api->PJRT_LoadedExecutable_Destroy(&args);
    }
    if (client) {
      PJRT_Client_Destroy_Args args;
      std::memset(&args, 0, sizeof(args));
      args.struct_size = PJRT_Client_Destroy_Args_STRUCT_SIZE;
      args.client = client;
      api->PJRT_Client_Destroy(&args);
    }
    // the plugin .so stays loaded (unloading PJRT plugins is unsafe)
  }

  std::string params_archive_;
  bool owner = true;
};

}  // namespace

// ----------------------------------------------------------------- C ABI

extern "C" {

const char* pd_predictor_last_error() { return g_last_error.c_str(); }

void* pd_predictor_create(const char* model_path, const char* plugin_path,
                          const char* options) {
  auto p = std::make_unique<Predictor>();
  if (!p->init(model_path, plugin_path, options ? options : ""))
    return nullptr;
  return p.release();
}

int pd_predictor_num_outputs(void* h) {
  return static_cast<int>(static_cast<Predictor*>(h)->num_outputs);
}

int pd_predictor_run(void* h, int num_inputs, void** in_data,
                     const int64_t* in_dims_flat, const int* in_ndims,
                     const int* in_dtypes) {
  return static_cast<Predictor*>(h)->run(num_inputs, in_data, in_dims_flat,
                                         in_ndims, in_dtypes)
             ? 0
             : -1;
}

int pd_predictor_output_info(void* h, int i, int64_t* dims, int max_dims,
                             int* ndims, int* dtype_code) {
  return static_cast<Predictor*>(h)->output_info(i, dims, max_dims, ndims,
                                                 dtype_code)
             ? 0
             : -1;
}

int pd_predictor_output_copy(void* h, int i, void* dst, int64_t dst_size) {
  return static_cast<Predictor*>(h)->output_copy(
             i, dst, static_cast<size_t>(dst_size))
             ? 0
             : -1;
}

void pd_predictor_destroy(void* h) { delete static_cast<Predictor*>(h); }

// Pool support (reference PredictorPool: clone the program, share the
// weights): a clone shares the PJRT client, the compiled executable, and
// the device-resident parameters with the owner, but keeps its OWN output
// buffers, so concurrent requests on different clones never race on
// results.  The owner must outlive its clones.
void* pd_predictor_clone(void* h) {
  auto* src = static_cast<Predictor*>(h);
  auto p = std::make_unique<Predictor>();
  p->dl = src->dl;
  p->api = src->api;
  p->client = src->client;
  p->device = src->device;
  p->exec = src->exec;
  p->num_params = src->num_params;
  p->num_outputs = src->num_outputs;
  p->param_bufs = src->param_bufs;
  p->owner = false;
  return p.release();
}

}  // extern "C"
